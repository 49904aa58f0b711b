"""Serving-tier tests: the health-gated router must complete every admitted
request bit-equal to the greedy reference even when chaos kills a replica
mid-decode (failover); probes must walk the replica state machine
(healthy → degraded → dead → resurrected) including the provably-dead
serve-job case; rolling hot-swap must drop nothing while ≥1 replica stays
dispatchable; deadline/shed/attempt-cap semantics are pinned; the daemon's
``serve_tier`` verb supervises and respawns crashed replica processes; and
the ``serving_tier_*`` metric schema is pinned as golden Prometheus text."""

import json
import os
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from distkeras_tpu import chaos, telemetry
from distkeras_tpu.checkpoint import CheckpointWatcher
from distkeras_tpu.job_deployment import Job, PunchcardServer
from distkeras_tpu.models import TransformerLM
from distkeras_tpu.models.generate import greedy_generate_module
from distkeras_tpu.serving import (
    GenerateRequest,
    GenerateResult,
    HttpReplica,
    QueueFull,
    ReplicaDead,
    ServingEngine,
    ServingTier,
    TierDeadline,
    TierExhausted,
    TierSaturated,
    install_tier_endpoint,
    tier_metrics,
    watch_and_swap,
)
from distkeras_tpu.telemetry.flightdeck import correlate
from distkeras_tpu.telemetry.flightdeck import server as server_mod
from distkeras_tpu.telemetry.metrics import Registry

VOCAB = 23
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")


@pytest.fixture(autouse=True)
def clean_tier(tmp_path, monkeypatch):
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    telemetry.configure(True)
    telemetry.metrics.reset()
    correlate.set_run_id("tiertest")
    chaos.configure("")  # each test starts with chaos off, counters clear
    yield
    chaos.configure(None)
    server_mod.stop()
    server_mod.configure(None)
    telemetry.metrics.reset()
    correlate.set_run_id(None)
    telemetry.configure(None)


@pytest.fixture(scope="module")
def lm():
    module = TransformerLM(vocab_size=VOCAB, dim=16, heads=2, num_layers=2,
                           max_len=32)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, 4), np.int32))["params"]
    return module, params


@pytest.fixture
def make_tier():
    """Tier factory that guarantees teardown (prober, watchers, engines)."""
    tiers = []

    def factory(replicas, **kw):
        kw.setdefault("registry", Registry())
        tier = ServingTier(replicas, **kw)
        tiers.append(tier)
        return tier

    yield factory
    for tier in tiers:
        tier.stop(close_replicas=True)


def _engines(lm, n, **kw):
    module, params = lm
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    return [ServingEngine(module, params, registry=Registry(), **kw)
            for _ in range(n)]


def _ref(module, params, prompt, steps):
    out = greedy_generate_module(
        module, params, np.asarray([prompt], np.int32), steps)
    return out[0, len(prompt):].tolist()


def _ctr(registry, name):
    entry = registry.snapshot().get(name)
    return 0.0 if entry is None else float(entry.get("value") or 0.0)


# ------------------------------------------------------------ metric schema


def test_tier_metrics_schema_golden():
    registry = Registry()
    m = tier_metrics(registry)
    m["requests"].inc(6)
    m["failovers"].inc(1)
    m["hedges"].inc(1)
    m["sheds"].inc(1)
    m["hot_swaps"].inc(2)
    m["roll_failures"].inc(1)
    m["deadline_expired"].inc(1)
    m["ckpt_rejected"].inc(1)
    m["replicas_healthy"].set(3)
    m["latency"].observe(0.25)
    m["attempts"].observe(1)
    m["attempts"].observe(3)
    golden = open(os.path.join(GOLDEN, "serving_tier_metrics.txt")).read()
    assert registry.to_prometheus(labels={"run_id": "fleet1234"}) == golden
    # get-or-create: a second call must hand back the same instruments
    assert tier_metrics(registry)["requests"] is m["requests"]


# ------------------------------------------------------- failover (chaos)


def test_failover_completes_bit_equal_under_chaos(lm, make_tier):
    """Acceptance: a replica chaos-killed mid-decode loses nothing — its
    in-flight requests re-run elsewhere and every admitted request
    completes bit-equal to the no-fault greedy reference."""
    module, params = lm
    registry = Registry()
    tier = make_tier(_engines(lm, 3), probe_interval=0.05,
                     default_deadline_s=120.0, registry=registry)
    tier.start()

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, size=n).tolist()
               for n in (3, 5, 4, 6, 3, 5)]
    refs = [_ref(module, params, p, 6) for p in prompts]

    # fire-once kill at the 2nd busy engine iteration: guaranteed to land
    # on a replica with requests actively decoding (never an idle loop)
    chaos.configure("11:kill_replica=2")
    results = [None] * len(prompts)

    def run(i):
        results[i] = tier.dispatch(
            GenerateRequest(prompt=prompts[i], max_new_tokens=6),
            deadline_s=120.0)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)

    for result, ref in zip(results, refs):
        assert result is not None and result.finish_reason != "aborted"
        assert result.tokens == ref  # bit-equal: f(params, prompt, seed)
    assert _ctr(registry, "serving_tier_failovers_total") >= 1
    assert list(tier.states().values()).count("dead") == 1
    # the kill provably fired (fire-once => exactly one dead) and is
    # visible on the telemetry registry for the CI chaos smoke to assert
    fired = telemetry.metrics.snapshot().get("chaos_kill_replica_total")
    assert fired and fired["value"] == 1


# ------------------------------------------------- probe state machine


def test_probe_walk_degraded_dead_resurrected(lm, make_tier):
    """Stalled health probes degrade a healthy replica; enough missed
    lease windows evict it to dead; a succeeding probe resurrects it."""
    fake = [0.0]
    registry = Registry()
    tier = make_tier(_engines(lm, 2, num_slots=1), probe_timeout=0.01,
                     probe_misses=2, clock=lambda: fake[0],
                     registry=registry)
    tier.probe_once()
    assert set(tier.states().values()) == {"healthy"}

    # stall every probe: both replicas stop heartbeating and degrade
    chaos.configure("7:stall_http=99,stall_secs=0.05")
    tier.probe_once()
    assert set(tier.states().values()) == {"degraded"}
    # a degraded replica still serves when no healthy one exists
    result = tier.dispatch(GenerateRequest(prompt=[1, 2, 3],
                                           max_new_tokens=2))
    assert result.finish_reason != "aborted"

    # the lease keeps draining while probes fail — sweep evicts to dead
    fake[0] += 60.0
    tier.probe_once()
    assert set(tier.states().values()) == {"dead"}
    with pytest.raises(TierSaturated):
        tier.dispatch(GenerateRequest(prompt=[1, 2], max_new_tokens=2))

    # dead is reversible for a merely-wedged replica (fleet rejoin)
    chaos.configure("")
    tier.probe_once()
    assert set(tier.states().values()) == {"healthy"}
    epoch = tier.snapshot()
    assert epoch["evictions"] >= 2 and epoch["healthy"] == 2


def test_dead_serve_job_is_replica_dead_immediately(make_tier):
    """A replica whose serve-job process the daemon reports dead is
    evicted on the next probe round — no /healthz timeout, no lease burn
    (the job check happens before any HTTP traffic)."""

    class _DeadJob:
        def status(self):
            return {"status": "failed", "returncode": 1}

    replica = HttpReplica("127.0.0.1:9", name="crashed", job=_DeadJob())
    with pytest.raises(ReplicaDead):
        replica.probe(timeout=0.1)

    tier = make_tier([replica])
    tier.probe_once()
    assert tier.states() == {"crashed": "dead"}
    assert tier.snapshot()["replicas"][0]["last_error"].startswith(
        "replica crashed: serve job is failed")


# -------------------------------------------------------- rolling hot-swap


def test_rolling_hot_swap_drops_nothing(lm, make_tier):
    """Roll the fleet to new params under live load: zero dropped
    requests, ≥1 replica dispatchable throughout, and every result is
    bit-equal to the old- or new-params reference (requests straddling
    the swap may land either side — never garbage, never aborted)."""
    module, params = lm
    params2 = module.init(jax.random.PRNGKey(9),
                          np.zeros((1, 4), np.int32))["params"]
    registry = Registry()
    tier = make_tier(_engines(lm, 2), probe_interval=0.05, registry=registry)
    tier.start()

    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, VOCAB, size=n).tolist()
               for n in (3, 4, 5, 3, 4, 5, 3, 4)]
    refs_old = [_ref(module, params, p, 5) for p in prompts]
    refs_new = [_ref(module, params2, p, 5) for p in prompts]
    assert refs_old != refs_new  # the swap must be observable

    results = [None] * len(prompts)
    min_healthy = [99]
    stop_sampling = threading.Event()

    def sample():
        while not stop_sampling.wait(0.01):
            min_healthy[0] = min(min_healthy[0],
                                 tier.snapshot()["healthy"])

    def run(i):
        results[i] = tier.dispatch(
            GenerateRequest(prompt=prompts[i], max_new_tokens=5),
            deadline_s=120.0)

    sampler = threading.Thread(target=sample)
    sampler.start()
    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    swapped = tier.roll(module, params2, timeout=60.0)
    for t in threads:
        t.join(timeout=120)
    stop_sampling.set()
    sampler.join(timeout=5)

    assert swapped == 2
    for i, result in enumerate(results):
        assert result is not None and result.finish_reason != "aborted"
        assert result.tokens in (refs_old[i], refs_new[i])
    assert min_healthy[0] >= 1  # never a moment with zero dispatchable
    assert _ctr(registry, "serving_tier_hot_swaps_total") == 2
    # post-roll traffic decodes under the new params on every replica
    for i in (0, 1):
        post = tier.dispatch(GenerateRequest(prompt=prompts[i],
                                             max_new_tokens=5))
        assert post.tokens == refs_new[i]


def _publish_step(tmp_path, step):
    """A committed AND published step: orbax-style final dir plus the
    manifest commit record the verified watcher requires."""
    from distkeras_tpu.checkpoint import write_manifest

    (tmp_path / f"step_{step}").mkdir()
    write_manifest(str(tmp_path), step)


def test_watch_and_swap_follows_committed_checkpoints(lm, tmp_path):
    """The replica-side watcher: a newly *published* step in the
    checkpoint directory hot-swaps the engine's params in place."""
    module, params = lm
    params2 = module.init(jax.random.PRNGKey(9),
                          np.zeros((1, 4), np.int32))["params"]
    registry = Registry()
    engine = ServingEngine(module, params, num_slots=2, page_size=8,
                           registry=registry)
    prompt = [1, 2, 3, 4]
    ref_new = _ref(module, params2, prompt, 4)
    _publish_step(tmp_path, 10)  # pre-existing: must NOT trigger a swap

    loaded = []

    def loader(step):
        loaded.append(step)
        return module, params2

    stopper = watch_and_swap(engine, str(tmp_path), loader,
                             poll_interval=0.02)
    try:
        time.sleep(0.1)
        assert loaded == []  # baselined at construction
        _publish_step(tmp_path, 12)  # a fresh publication
        deadline = time.monotonic() + 30
        while (_ctr(registry, "serving_hot_swaps_total") < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
    finally:
        stopper()
    assert loaded == [12]
    result = engine.generate(prompt, max_new_tokens=4)
    assert result.tokens == ref_new
    engine.stop()


def test_watch_and_swap_survives_raising_poll(lm, tmp_path, monkeypatch):
    """DK121 regression: a transient poll/verify error (fs flake, torn
    manifest) must not kill the watcher thread — the next round re-polls
    and a later publication still swaps."""
    from distkeras_tpu import checkpoint as ckpt_mod

    module, params = lm
    params2 = module.init(jax.random.PRNGKey(9),
                          np.zeros((1, 4), np.int32))["params"]
    registry = Registry()
    engine = ServingEngine(module, params, num_slots=2, page_size=8,
                           registry=registry)
    real_poll = ckpt_mod.CheckpointWatcher.poll
    calls = []

    def flaky_poll(self):
        calls.append(1)
        if len(calls) % 2 == 1:  # every other round blows up
            raise RuntimeError("transient fs flake")
        return real_poll(self)

    monkeypatch.setattr(ckpt_mod.CheckpointWatcher, "poll", flaky_poll)
    stopper = watch_and_swap(engine, str(tmp_path),
                             lambda step: (module, params2),
                             poll_interval=0.02)
    try:
        _publish_step(tmp_path, 12)
        deadline = time.monotonic() + 30
        while (_ctr(registry, "serving_hot_swaps_total") < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
    finally:
        stopper()
    assert _ctr(registry, "serving_hot_swaps_total") == 1
    assert len(calls) >= 2  # the raising rounds did not kill the watcher
    engine.stop()


def test_probe_loop_survives_probe_exception(lm, make_tier, monkeypatch):
    """DK121 regression: an exception escaping a probe round (e.g. a
    failed sweep/export) must not kill the supervision thread."""
    tier = make_tier(_engines(lm, 1), probe_interval=0.01)
    calls = []
    real = ServingTier.probe_once

    def flaky(self):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("export flaked")
        return real(self)

    monkeypatch.setattr(ServingTier, "probe_once", flaky)
    tier.start()  # round 1 runs synchronously and succeeds
    deadline = time.monotonic() + 30
    while len(calls) < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(calls) >= 4  # round 2 raised; rounds 3+ still happened
    with tier._cv:
        thread = tier._probe_thread
    assert thread is not None and thread.is_alive()


def test_checkpoint_watcher_reports_newest_once(tmp_path):
    _publish_step(tmp_path, 3)
    watcher = CheckpointWatcher(str(tmp_path))
    assert watcher.poll() is None  # baselined at the pre-existing step
    _publish_step(tmp_path, 7)
    assert watcher.poll() == 7
    assert watcher.poll() is None  # reported once
    _publish_step(tmp_path, 5)  # older than anything reported
    assert watcher.poll() is None
    assert CheckpointWatcher(str(tmp_path), start_after=-1).poll() == 7
    # a bare orbax dir with no manifest (in-flight save, crashed publish)
    # is invisible: never surfaced, however new it is
    (tmp_path / "step_9").mkdir()
    assert watcher.poll() is None


# --------------------------------------- deadline / shedding / attempt cap


class _StubHandle:
    def __init__(self, result):
        self._result = result

    def result(self, timeout=None):
        return self._result


class _StubReplica:
    """Scriptable replica: fixed probe stats, queued submit outcomes."""

    def __init__(self, name, stats=None, outcomes=None):
        self.name = name
        self.stats = stats or {}
        self.outcomes = list(outcomes or [])
        self.submitted = []

    def probe(self, timeout=1.0):
        return dict(self.stats)

    def submit(self, request):
        self.submitted.append(request)
        outcome = self.outcomes.pop(0) if self.outcomes else "ok"
        if isinstance(outcome, Exception):
            raise outcome
        if outcome == "ok":
            return _StubHandle(GenerateResult(
                request_id=request.request_id, prompt=request.prompt,
                tokens=[7], finish_reason="length"))
        return _StubHandle(GenerateResult(
            request_id=request.request_id, prompt=request.prompt,
            tokens=[], finish_reason="aborted"))

    def cancel(self, handle):
        return True

    def close(self):
        pass


def test_deadline_expires_at_the_router(make_tier):
    registry = Registry()
    tier = make_tier([_StubReplica("a")], registry=registry)
    with pytest.raises(TierDeadline):
        tier.dispatch(GenerateRequest(prompt=[1], max_new_tokens=2),
                      deadline_s=0.0)
    assert _ctr(registry, "serving_tier_deadline_expired_total") == 1


def test_saturated_tier_sheds(make_tier):
    registry = Registry()
    tier = make_tier([_StubReplica("a", outcomes=[QueueFull("full")])],
                     registry=registry)
    with pytest.raises(TierSaturated):
        tier.dispatch(GenerateRequest(prompt=[1], max_new_tokens=2))
    assert _ctr(registry, "serving_tier_sheds_total") == 1


def test_attempt_cap_exhausts(make_tier):
    """A replica that keeps aborting burns the attempt cap -> 502, with
    each retry counted as a failover."""
    registry = Registry()
    rep = _StubReplica("a", outcomes=["aborted"] * 5)
    tier = make_tier([rep], max_attempts=3, backoff_s=0.001,
                     backoff_cap_s=0.002, registry=registry)
    with pytest.raises(TierExhausted):
        tier.dispatch(GenerateRequest(prompt=[1], max_new_tokens=2),
                      deadline_s=30.0)
    assert len(rep.submitted) == 3
    assert _ctr(registry, "serving_tier_failovers_total") == 3


def test_least_loaded_dispatch_prefers_idle_replica(make_tier):
    busy = _StubReplica("busy", stats={"queue_depth": 5, "active_slots": 2})
    idle = _StubReplica("idle", stats={"queue_depth": 0, "active_slots": 0})
    tier = make_tier([busy, idle])
    result = tier.dispatch(GenerateRequest(prompt=[1], max_new_tokens=2))
    assert result.finish_reason == "length"
    assert not busy.submitted and len(idle.submitted) == 1


def test_request_id_is_stable_across_failover(make_tier):
    """The idempotency key: every hop of one request carries the same id."""
    rep = _StubReplica("a", outcomes=["aborted", "ok"])
    tier = make_tier([rep], backoff_s=0.001, backoff_cap_s=0.002)
    tier.dispatch(GenerateRequest(prompt=[1], max_new_tokens=2),
                  deadline_s=30.0)
    assert len(rep.submitted) == 2
    ids = {r.request_id for r in rep.submitted}
    assert len(ids) == 1 and ids != {""}
    # and the propagated per-hop budget rides timeout_s
    assert all(r.timeout_s and r.timeout_s <= 30.0 for r in rep.submitted)


# ------------------------------------------------------- request validation


def test_request_validation_bounds():
    GenerateRequest(prompt=[1], top_p=0.5).validate()  # nucleus in range
    with pytest.raises(ValueError):
        GenerateRequest(prompt=[1], top_k=-1).validate()
    with pytest.raises(ValueError):
        GenerateRequest(prompt=[1], top_p=1.5).validate()
    with pytest.raises(ValueError):
        GenerateRequest(prompt=[1], top_p=-0.1).validate()
    with pytest.raises(ValueError):
        GenerateRequest(prompt=[1], timeout_s=0.0).validate()


# ------------------------------------------------------------ HTTP endpoint


def test_tier_endpoint_routes_and_reports(lm, make_tier):
    module, params = lm
    server_mod.configure(0)
    addr = telemetry.flightdeck.ensure_server()
    tier = make_tier(_engines(lm, 2))
    install_tier_endpoint(tier)

    prompt = [2, 4, 6]
    ref = _ref(module, params, prompt, 4)
    body = json.dumps({"prompt": prompt, "max_new_tokens": 4}).encode()
    req = urllib.request.Request(
        f"http://{addr}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        payload = json.loads(resp.read().decode("utf-8"))
    assert payload["tokens"] == ref
    assert payload["finish_reason"] in ("length", "eos")

    with urllib.request.urlopen(f"http://{addr}/tier", timeout=10) as resp:
        snap = json.loads(resp.read().decode("utf-8"))
    assert snap["healthy"] == 2
    assert [r["state"] for r in snap["replicas"]] == ["healthy"] * 2


# --------------------------------------------------------- daemon tier verbs


@pytest.fixture
def punchcard():
    server = PunchcardServer(port=0, secret="s3cret")
    server.start()
    yield server
    server.stop()


def test_serve_tier_verb_and_status(punchcard):
    job = Job("127.0.0.1", punchcard.port, secret="s3cret",
              script="import time\ntime.sleep(60)\n")
    tier_id = job.serve_tier(replicas=2)
    st = job.tier_status()
    assert st["status"] == "ok" and st["tier_id"] == tier_id
    assert len(st["replicas"]) == 2 and st["serving"] == 2
    assert st["respawns"] == 0

    stopped = job.stop_tier()
    assert stopped == {"status": "stopped", "tier_id": tier_id, "stopped": 2}
    assert job.tier_status(tier_id)["status"] == "unknown"
    # the replicas' job records survive as stopped serve jobs
    statuses = [punchcard.jobs[r["job_id"]]["status"]
                for r in st["replicas"]]
    assert statuses == ["stopped", "stopped"]


def test_serve_tier_respawns_crashed_replicas_up_to_cap(punchcard):
    """Replica supervision: the runner loop detects a dead serve-job Popen
    within its idle wakeup, respawns it into the same tier slot, and stops
    at the respawn cap (the corpse then stays visible as failed)."""
    job = Job("127.0.0.1", punchcard.port, secret="s3cret",
              script="raise SystemExit(1)\n")
    job.serve_tier(replicas=1, max_respawns=2)
    deadline = time.monotonic() + 30
    st = job.tier_status()
    while time.monotonic() < deadline:
        st = job.tier_status()
        if st["respawns"] == 2 and st["replicas"][0]["status"] == "failed":
            break
        time.sleep(0.2)
    assert st["respawns"] == 2 and st["max_respawns"] == 2
    assert st["replicas"][0]["status"] == "failed"
    assert st["serving"] == 0


def test_serve_tier_idempotent_retry(punchcard, monkeypatch):
    """A lost serve_tier reply must not double-spawn the fleet: the retry
    replays the original tier (same id, same job_ids)."""
    # The daemon replays a retry that comes AFTER it answered (a lost reply;
    # ``job_deployment`` guards the sequential case only).  The injected drop
    # fires as soon as the request is sent, so the backoff has to outlast the
    # daemon's two process spawns: at 10 ms a loaded box let the retry in
    # first and the tier was spawned twice.
    job = Job("127.0.0.1", punchcard.port, secret="s3cret",
              script="import time\ntime.sleep(60)\n", rpc_backoff=0.5)
    chaos.configure("5:drop_reply=1")
    tier_id = job.serve_tier(replicas=2)
    chaos.configure("")
    st = job.tier_status()
    assert st["serving"] == 2 and len(punchcard._tiers) == 1
    assert set(punchcard._tiers) == {tier_id}
    job.stop_tier()
