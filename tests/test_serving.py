"""Serving-engine tests: continuous batching must emit tokens bitwise
identical to ``greedy_generate`` under staggered concurrent arrival with ONE
compiled decode step (retrace pin via ``install_jax_hooks``); slots and KV
pages retire and get reused; seeded sampling is deterministic and independent
of co-batched traffic; the bounded queue sheds load at admission; and the SLO
metrics schema is pinned three ways — golden Prometheus text, a live
flightdeck ``/metrics`` scrape, and the ``/generate`` HTTP endpoint."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import LatentMoELM, StagedLM, TransformerLM
from distkeras_tpu.models.generate import (
    greedy_generate_module,
    greedy_generate_staged,
)
from distkeras_tpu.serving import (
    GenerateRequest,
    PagedKVCache,
    QueueFull,
    ServingEngine,
    install_http_endpoint,
    serving_metrics,
)
from distkeras_tpu.telemetry.flightdeck import correlate
from distkeras_tpu.telemetry.flightdeck import server as server_mod
from distkeras_tpu.telemetry.metrics import Registry, install_jax_hooks

VOCAB = 23
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")


@pytest.fixture(autouse=True)
def clean_serving(tmp_path, monkeypatch):
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    telemetry.configure(True)
    telemetry.metrics.reset()
    correlate.set_run_id("servetest")
    yield
    server_mod.stop()
    server_mod.configure(None)
    telemetry.metrics.reset()
    correlate.set_run_id(None)
    telemetry.configure(None)


@pytest.fixture(scope="module")
def lm():
    """One tiny TransformerLM + params shared by the whole module (engines
    recompile per instance; the params don't need to)."""
    module = TransformerLM(vocab_size=VOCAB, dim=16, heads=2, num_layers=2,
                           max_len=32)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, 4), np.int32))["params"]
    return module, params


@pytest.fixture(scope="module")
def shared_engine(lm):
    """One engine (private registry) shared by every test that doesn't need
    a special configuration: the prefill/decode programs compile once for
    the whole module, and reuse across tests doubles as an endurance check —
    slots, pages, and per-request RNG chains must come back clean between
    tests."""
    module, params = lm
    engine = ServingEngine(module, params, num_slots=3, page_size=8,
                           registry=Registry())
    yield engine
    engine.stop()


@pytest.fixture
def make_engine():
    """Engine factory that guarantees ``stop()`` at teardown.  Default
    registry is a private one so tests don't cross-pollute the global
    scrape; pass ``registry=None`` explicitly to use the global."""
    engines = []

    def factory(model, params, **kw):
        kw.setdefault("registry", Registry())
        engine = ServingEngine(model, params, **kw)
        engines.append(engine)
        return engine

    yield factory
    for engine in engines:
        engine.stop()


def _ref(module, params, prompt, steps):
    """Per-request reference continuation from the lockstep greedy decoder."""
    out = greedy_generate_module(
        module, params, np.asarray([prompt], np.int32), steps
    )
    return out[0, len(prompt):].tolist()


def _get(addr, path, timeout=30):
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=timeout) as r:
        return r.status, r.read().decode("utf-8")


def _post(addr, path, payload, timeout=30):
    req = urllib.request.Request(
        f"http://{addr}{path}", data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode("utf-8")


# ------------------------------------------------------------- paged cache


def test_paged_cache_alloc_free_cycle():
    cache = PagedKVCache(num_layers=1, num_slots=2, page_size=4,
                         pages_per_slot=3, heads=2, head_dim=4)
    total = cache.pages_free
    assert total == 2 * 3  # default pool: full context per slot, + scratch
    assert cache.pages_needed(5) == 2
    assert cache.max_context() == 12

    cache.alloc(0, 2)
    assert cache.pages_in_use == 2
    assert (cache.tables[0, :2] > 0).all()  # real pages, never scratch
    assert cache.tables[0, 2] == 0          # unallocated entry -> scratch
    cache.alloc(1, 3)
    assert not cache.can_alloc(total)
    with pytest.raises(ValueError, match="table size"):
        cache.alloc(0, 2)  # would overflow slot 0's table

    assert cache.free(0) == 2
    assert (cache.tables[0] == 0).all()
    cache.free(1)
    assert cache.pages_in_use == 0 and cache.pages_free == total


# ----------------------------------------------------- greedy token identity


def test_staggered_concurrent_greedy_matches_greedy_generate(lm,
                                                             shared_engine):
    """Acceptance: >=3 requests admitted while others are mid-decode emit
    exactly the tokens the per-request lockstep decoder emits."""
    module, params = lm
    engine = shared_engine
    rng = np.random.default_rng(1)
    lengths = (3, 7, 5)
    steps = (8, 6, 10)
    prompts = [rng.integers(0, VOCAB, size=n).tolist() for n in lengths]
    refs = [_ref(module, params, p, s) for p, s in zip(prompts, steps)]

    pendings = []
    for prompt, s in zip(prompts, steps):
        pendings.append(
            engine.submit(GenerateRequest(prompt=prompt, max_new_tokens=s))
        )
        time.sleep(0.02)  # stagger: later requests join a running batch
    results = [p.result(timeout=120) for p in pendings]

    for result, ref, prompt in zip(results, refs, prompts):
        assert result is not None and result.finish_reason == "length"
        assert result.tokens == ref
        assert result.prompt == prompt
        assert result.ttft_s > 0 and result.latency_s >= result.ttft_s


def test_staged_lm_tokens_match(make_engine):
    module = StagedLM(vocab_size=VOCAB, dim=16, heads=2, num_stages=2,
                      blocks_per_stage=1, max_len=32)
    params, _ = module.init(jax.random.PRNGKey(1), np.zeros((1, 4), np.int32))
    prompt = [3, 1, 4, 1, 5]
    ref = greedy_generate_staged(
        module, params, np.asarray([prompt], np.int32), 6
    )[0, len(prompt):].tolist()
    engine = make_engine(module, params, num_slots=2, page_size=8)
    result = engine.generate(prompt, max_new_tokens=6, timeout=120)
    assert result.tokens == ref


def test_slot_retirement_and_reuse(lm, shared_engine):
    """More requests than slots: every slot must retire and be re-admitted
    into, and every KV page must come back to the pool."""
    module, params = lm
    engine = shared_engine
    rng = np.random.default_rng(2)
    # twice as many requests as slots; lengths cycle through two values so
    # the lockstep reference decoder compiles only two programs
    prompts = [rng.integers(0, VOCAB, size=n).tolist()
               for n in (3, 5, 3, 5, 3, 5)]
    refs = [_ref(module, params, p, 5) for p in prompts]
    pendings = [engine.submit(GenerateRequest(prompt=p, max_new_tokens=5))
                for p in prompts]
    results = [p.result(timeout=120) for p in pendings]
    assert [r.tokens for r in results] == refs

    deadline = time.monotonic() + 10
    while engine.stats()["active_slots"] and time.monotonic() < deadline:
        time.sleep(0.01)
    stats = engine.stats()
    assert stats["active_slots"] == 0 and stats["pages_in_use"] == 0


def test_eos_retires_early(lm, shared_engine):
    module, params = lm
    engine = shared_engine
    prompt = [2, 7, 1, 8, 4]  # length 5: reference program already compiled
    ref = _ref(module, params, prompt, 10)
    eos = ref[3]
    k = ref.index(eos)  # first emission of the eos token
    result = engine.generate(prompt, max_new_tokens=10, eos_id=eos,
                             timeout=120)
    assert result.finish_reason == "eos"
    assert result.tokens == ref[:k + 1]


# ------------------------------------------------------------------ sampling


def test_seeded_sampling_deterministic_and_traffic_independent(
        lm, shared_engine):
    module, params = lm
    engine = shared_engine
    prompt = [5, 9, 2]
    knobs = dict(max_new_tokens=8, temperature=0.9, top_k=7, top_p=0.95,
                 seed=123, timeout=120)
    alone = engine.generate(prompt, **knobs)
    assert engine.generate(prompt, **knobs).tokens == alone.tokens

    other_seed = engine.generate(prompt, **{**knobs, "seed": 7})
    assert other_seed.tokens != alone.tokens

    # same request co-batched with greedy traffic: tokens must not change
    # (each request's RNG chain splits only on its own tokens)
    rng = np.random.default_rng(3)
    noise = [engine.submit(GenerateRequest(
        prompt=rng.integers(0, VOCAB, size=6).tolist(), max_new_tokens=10))
        for _ in range(2)]
    busy = engine.generate(prompt, **knobs)
    assert busy.tokens == alone.tokens
    assert all(p.result(timeout=120) is not None for p in noise)


# -------------------------------------------------------------- backpressure


def test_queue_backpressure_rejects_and_counts(lm, make_engine):
    module, params = lm
    registry = Registry()
    engine = make_engine(module, params, queue_size=2, registry=registry)
    engine.start = lambda: None  # hold the loop: the queue cannot drain
    held = [engine.submit(GenerateRequest(prompt=[1, 2], max_new_tokens=2))
            for _ in range(2)]
    with pytest.raises(QueueFull):
        engine.submit(GenerateRequest(prompt=[1, 2], max_new_tokens=2))
    snap = registry.snapshot()
    assert snap["serving_requests_rejected_total"]["value"] == 1.0
    assert snap["serving_queue_depth"]["value"] == 2.0

    del engine.start  # restore the class method; held requests drain
    engine.start()
    results = [p.result(timeout=120) for p in held]
    assert all(r is not None and r.finish_reason == "length" for r in results)


def test_unservable_requests_rejected_loudly(lm, shared_engine):
    module, params = lm
    engine = shared_engine  # width == max_len == 32
    with pytest.raises(ValueError, match="prompt length"):
        engine.submit(GenerateRequest(prompt=list(range(32))))
    with pytest.raises(ValueError, match="vocabulary"):
        engine.submit(GenerateRequest(prompt=[VOCAB + 5]))
    with pytest.raises(ValueError, match="non-empty"):
        engine.submit(GenerateRequest(prompt=[]))


# ------------------------------------------------------------- retrace pin


def test_one_compiled_decode_step_across_staggered_traffic(lm,
                                                           shared_engine):
    """Acceptance: after one warmup request per prefill bucket, arbitrary
    mixes of prompt lengths, sampling knobs, and EOS must add ZERO jax
    compile/trace events — admitting a request is data movement and a
    bucket hit, never a retrace (DK102)."""
    module, params = lm
    install_jax_hooks()
    # a throwaway compile proves the hook is live (the counter only exists
    # once an event fires — the shared engine may already be warm)
    probe = jax.jit(lambda x: x + 1)
    probe(np.ones(3))
    engine = shared_engine
    # warm every bucket the traffic below can hit (page_size=8 ladder:
    # lengths <=8 -> bucket 8, lengths 9..16 -> bucket 16)
    engine.generate([1, 2, 3], max_new_tokens=3, timeout=120)
    engine.generate(list(range(1, 11)), max_new_tokens=3, timeout=120)

    base = telemetry.metrics.snapshot()["jax_compiles_total"]["value"]
    assert base >= 1
    rng = np.random.default_rng(4)
    pendings = []
    for i, n in enumerate((2, 8, 5, 11, 3)):
        pendings.append(engine.submit(GenerateRequest(
            prompt=rng.integers(0, VOCAB, size=n).tolist(),
            max_new_tokens=4 + i,
            temperature=0.0 if i % 2 else 0.8,
            top_k=5 if i == 2 else 0,
            top_p=0.9 if i == 3 else 1.0,
            seed=i,
            eos_id=(1 if i == 4 else None),
        )))
        time.sleep(0.01)
    assert all(p.result(timeout=120) is not None for p in pendings)
    after = telemetry.metrics.snapshot()["jax_compiles_total"]["value"]
    assert after == base, f"{after - base} recompiles after warmup"


# ------------------------------------------------------------------ metrics


def test_serving_metrics_schema_golden():
    """The SLO instrument schema (names, help text, bucket ladder) rendered
    as Prometheus text is pinned byte-for-byte."""
    registry = Registry()
    m = serving_metrics(registry)
    m["ttft"].observe(0.004)
    m["ttft"].observe(0.12)
    for _ in range(3):
        m["token_latency"].observe(0.0008)
    m["queue_depth"].set(2)
    m["active_slots"].set(3)
    m["pages_in_use"].set(12)
    m["tokens"].inc(42)
    m["requests"].inc(5)
    m["rejected"].inc(1)
    m["prefill_seconds"].observe(0.006)
    m["prefill_padded"].inc(13)
    m["decode_steps"].inc(17)
    m["decode_chained"].inc(15)
    m["decode_sampled"].inc(4)
    m["decode_sorted"].inc(3)
    m["spec_proposed"].inc(24)
    m["spec_accepted"].inc(19)
    m["hot_swaps"].inc(2)
    m["kv_read"].inc(768)
    m["kv_capacity"].inc(1024)
    # the loop thread's account of its own time
    m["loop_iteration"].observe(0.011)
    m["loop_dispatch"].observe(0.0012)
    for _ in range(2):
        m["loop_wait"].observe(0.004)
        m["loop_emit"].observe(0.0003)
    m["loop_idle"].observe(0.05)
    m["queue_wait"].observe(1.9)
    m["dispatches"].inc(21)
    m["dispatches_starved"].inc(2)
    m["gc_pause"].inc(0.03)
    # and the counters that a block with experts brings itself
    block = LatentMoELM(vocab_size=8, max_len=8).decode_spec(None).instruments(
        registry)
    block["assignments"].inc(96)
    block["held"].inc(24)
    block["load"].observe(1.5)
    block["tiles"].inc(7)
    block["touched"].inc(6)
    golden = open(os.path.join(GOLDEN, "serving_metrics.txt")).read()
    assert registry.to_prometheus(labels={"run_id": "fleet1234"}) == golden
    # get-or-create: a second call must hand back the same instruments
    assert serving_metrics(registry)["tokens"] is m["tokens"]


def test_flightdeck_scrape_and_generate_endpoint(lm, make_engine):
    """Acceptance: with the engine on the global registry and the exporter
    live, concurrent ``/generate`` calls answer with the greedy-reference
    tokens and the ``/metrics`` scrape carries non-empty SLO histograms."""
    module, params = lm
    server_mod.configure(0)
    addr = telemetry.flightdeck.ensure_server()
    engine = make_engine(module, params, num_slots=3, page_size=8,
                         registry=None)  # global registry -> the scrape
    install_http_endpoint(engine)

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, VOCAB, size=n).tolist() for n in (3, 5, 4)]
    refs = [_ref(module, params, p, 5) for p in prompts]
    replies = [None] * len(prompts)

    def call(i):
        status, text = _post(addr, "/generate",
                             {"prompt": prompts[i], "max_new_tokens": 5})
        replies[i] = (status, json.loads(text))

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(prompts))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    for (status, body), ref in zip(replies, refs):
        assert status == 200 and body["tokens"] == ref
        assert body["finish_reason"] == "length"

    # GET with query parameters rides the same endpoint
    status, text = _get(addr, "/generate?prompt=1,2,3&max_new_tokens=2")
    assert status == 200 and len(json.loads(text)["tokens"]) == 2
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(addr, "/generate?max_new_tokens=2")  # no prompt
    assert err.value.code == 400

    status, text = _get(addr, "/metrics")
    assert status == 200
    assert 'serving_ttft_seconds_bucket{' in text
    assert 'serving_token_latency_seconds_bucket{' in text
    for line in text.splitlines():
        if line.startswith('serving_ttft_seconds_count{run_id="servetest"}'):
            assert float(line.split()[-1]) >= 4  # 3 POST + 1 GET
            break
    else:
        pytest.fail("serving_ttft_seconds_count missing from scrape")
    assert 'serving_queue_depth{run_id="servetest"}' in text
    assert 'serving_tokens_total{run_id="servetest"}' in text


def test_model_predictor_routes_through_engine(lm, shared_engine):
    """``ModelPredictor(engine=...)``: frame rows become prompts; the
    prediction column carries the greedy continuations, token-identical
    to the per-request reference."""
    from distkeras_tpu.frame import DataFrame
    from distkeras_tpu.predictors import ModelPredictor

    module, params = lm
    engine = shared_engine
    rng = np.random.default_rng(6)
    prompts = rng.integers(0, VOCAB, size=(5, 4)).astype(np.int32)
    refs = [_ref(module, params, row.tolist(), 3) for row in prompts]

    predictor = ModelPredictor(engine=engine, max_new_tokens=3)
    out = predictor.predict(DataFrame({"features": prompts}))
    assert [list(v) for v in out.column("prediction")] == refs
    assert predictor.last_mode == "engine"
    with pytest.raises(TypeError, match="engine"):
        ModelPredictor()  # neither a model nor an engine


_SERVE_SCRIPT = """\
import json
import time

from distkeras_tpu import serving, telemetry

telemetry.flightdeck.activate()
with open("flags_out.json", "w") as f:
    json.dump(serving.serve_flags(), f)  # prove the env round-trip
time.sleep(120)  # a serving loop never exits; stop_serving terminates us
"""


def test_daemon_serve_verb_lifecycle(tmp_path, monkeypatch):
    """``serve`` launches a detached long-running job with the flightdeck
    forced on; ``serving_address`` discovers its exporter; engine knobs
    passed as ``Job.serve(flags=...)`` reach the child via
    ``DISTKERAS_SERVE_FLAGS`` / ``serving.serve_flags()`` and echo in the
    status reply; ``stop_serving`` terminates it and the status flips to
    ``stopped``."""
    from distkeras_tpu.job_deployment import Job, PunchcardServer

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("PYTHONPATH", repo)
    server = PunchcardServer(port=0, secret="s3cret")
    server.start()
    flags = {"spec_tokens": 3, "prefill_buckets": [8, 32], "num_slots": 2}
    try:
        job = Job("127.0.0.1", server.port, secret="s3cret",
                  script=_SERVE_SCRIPT)
        assert job.serve(flags=flags)
        addr = job.serving_address(timeout=60)
        status, text = _get(addr, "/healthz")
        assert status == 200 and json.loads(text)["status"] == "ok"
        assert job.status()["serve_flags"] == flags
        flags_out = os.path.join(server.workdir, "flags_out.json")
        deadline = time.monotonic() + 30
        while not os.path.exists(flags_out) and time.monotonic() < deadline:
            time.sleep(0.05)
        with open(flags_out) as f:
            assert json.load(f) == flags  # the child saw the same knobs
        reply = job.stop_serving()
        assert reply == {"status": "stopped", "job_id": job.job_id}
        assert job.status()["status"] == "stopped"
    finally:
        server.stop()


def test_stop_aborts_in_flight_and_queued(lm, make_engine):
    module, params = lm
    engine = make_engine(module, params, num_slots=1, queue_size=8)
    pendings = [engine.submit(GenerateRequest(
        prompt=[1, 2, 3], max_new_tokens=20)) for _ in range(3)]
    engine.stop()
    results = [p.result(timeout=10) for p in pendings]
    assert all(r is not None for r in results)
    assert any(r.finish_reason == "aborted" for r in results)
    assert all(r.finish_reason in ("aborted", "length", "eos")
               for r in results)
