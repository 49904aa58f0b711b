"""Serving fast-path tests: prefill width bucketing, speculative decoding,
and the tensor-parallel (sharded) decode step.

The pins that matter:

* greedy speculative output is **bitwise identical** to the non-speculative
  greedy stream — for TransformerLM and StagedLM, under staggered
  concurrent arrival, regardless of draft quality;
* a faithful draft (draft == target) accepts everything, so the
  decode-steps-per-token ratio measured by the new counters drops below 1;
* bucketed prefill admits without retracing (one program per *used*
  bucket), and ``serving_prefill_padded_tokens`` records less padding than
  the single-bucket baseline would;
* the sharded engine on the 8-device CPU mesh emits the same greedy tokens
  as the unsharded one (token-equal; psum reassociation means bitwise
  equality is not promised *across* mesh configs, while speculative vs
  plain *within* one config stays bitwise);
* alloc/free churn never leaks pages, and the multi-token append/rollback
  helpers respect page ownership and capacity.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import StagedLM, TransformerLM
from distkeras_tpu.models.generate import greedy_generate_module
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.serving import (
    GenerateRequest,
    PagedKVCache,
    ServingEngine,
    append_rows,
    modified_probs,
    rollback_rows,
    speculative_verify,
)
from distkeras_tpu.telemetry.metrics import Registry, install_jax_hooks

VOCAB = 23


@pytest.fixture(autouse=True)
def clean_serving(tmp_path, monkeypatch):
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    telemetry.configure(True)
    telemetry.metrics.reset()
    yield
    telemetry.metrics.reset()
    telemetry.configure(None)


@pytest.fixture(scope="module")
def lm():
    module = TransformerLM(vocab_size=VOCAB, dim=16, heads=2, num_layers=2,
                           max_len=32)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, 4), np.int32))["params"]
    return module, params


@pytest.fixture(scope="module")
def draft_lm():
    """The shallow draft: same vocab/dim/max_len, one layer."""
    module = TransformerLM(vocab_size=VOCAB, dim=16, heads=2, num_layers=1,
                           max_len=32)
    params = module.init(jax.random.PRNGKey(1),
                         np.zeros((1, 4), np.int32))["params"]
    return module, params


@pytest.fixture
def make_engine():
    engines = []

    def factory(model, params, **kw):
        kw.setdefault("registry", Registry())
        engine = ServingEngine(model, params, **kw)
        engines.append(engine)
        return engine

    yield factory
    for engine in engines:
        engine.stop()


# Engine construction compiles real XLA programs, so the common
# configurations are shared module-wide (tests read counter DELTAS off the
# shared registries; the engines are stateless between requests by the
# churn invariant pinned at the bottom of this file).


@pytest.fixture(scope="module")
def plain_engine(lm):
    module, params = lm
    registry = Registry()
    engine = ServingEngine(module, params, num_slots=3, page_size=8,
                           registry=registry)
    yield engine, registry
    engine.stop()


@pytest.fixture(scope="module")
def spec_engine(lm, draft_lm):
    """Speculative engine with the shallow (frequently wrong) draft."""
    module, params = lm
    dmodule, dparams = draft_lm
    registry = Registry()
    engine = ServingEngine(module, params, num_slots=3, page_size=8,
                           draft_model=dmodule, draft_params=dparams,
                           spec_tokens=3, registry=registry)
    yield engine, registry
    engine.stop()


@pytest.fixture(scope="module")
def faithful_engine(lm):
    """Speculative engine whose draft IS the target: accepts everything."""
    module, params = lm
    registry = Registry()
    engine = ServingEngine(module, params, num_slots=3, page_size=8,
                           draft_model=module, draft_params=params,
                           spec_tokens=3, registry=registry)
    yield engine, registry
    engine.stop()


def _ref(module, params, prompt, steps):
    out = greedy_generate_module(
        module, params, np.asarray([prompt], np.int32), steps
    )
    return out[0, len(prompt):].tolist()


# ------------------------------------------------------- verify unit tests


def _judge(logits, drafts, qprobs, temperature, speculate=True, seed=0):
    out, count, accepted, _ = speculative_verify(
        jnp.asarray(logits), jnp.asarray(drafts, jnp.int32),
        jnp.asarray(qprobs), jax.random.PRNGKey(seed),
        jnp.float32(temperature), jnp.int32(0), jnp.float32(1.0),
        jnp.asarray(speculate))
    return (np.asarray(out), int(count), int(accepted))


def test_speculative_verify_greedy_accept_prefix():
    """Greedy judging: accept while draft == argmax; every emitted token is
    a target argmax row, and the correction token caps the window."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4, 11)).astype(np.float32)
    targets = logits.argmax(-1)
    qprobs = np.full((4, 11), 1.0 / 11, np.float32)

    drafts = targets.copy()
    drafts[2] = (targets[2] + 1) % 11  # first mismatch at row 2
    out, count, accepted = _judge(logits, drafts, qprobs, 0.0)
    assert (count, accepted) == (3, 2)
    assert out[:3].tolist() == targets[:3].tolist()

    out, count, accepted = _judge(logits, targets, qprobs, 0.0)
    assert (count, accepted) == (4, 4)  # all-accept: no bonus token
    assert out.tolist() == targets.tolist()

    out, count, accepted = _judge(logits, targets, qprobs, 0.0,
                                  speculate=False)
    assert (count, accepted) == (1, 0)  # opted out: plain single-token path
    assert out[0] == targets[0]


def test_speculative_verify_faithful_draft_accepts_all_stochastic():
    """With q == p the acceptance test is u < 1 — always true — so a
    faithful draft is fully accepted in the stochastic regime too."""
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(3, 7)).astype(np.float32)
    temp = 0.8
    p = np.asarray(jax.vmap(
        modified_probs, in_axes=(0, None, None, None))(
            jnp.asarray(logits), jnp.float32(temp), jnp.int32(0),
            jnp.float32(1.0)))
    drafts = p.argmax(-1)  # any in-support proposal works
    out, count, accepted = _judge(logits, drafts, p, temp, seed=3)
    assert (count, accepted) == (3, 3)
    assert out.tolist() == drafts.tolist()


def test_spec_key_derivation_decorrelated_from_plain_chain():
    """Regression pin for the key-lineage fix: the speculative keys derive
    from the fresh ``next_plain`` subkey, never from the parent ``key``.
    Under partitionable threefry (the default in newer JAX) the old
    derivation collided *exactly* — ``split(key, 2m+1)[:2] == split(key)``,
    so the first accept-uniform reused the plain sampling key."""
    m = 3
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        key = jax.random.PRNGKey(42)
        wide = np.asarray(jax.random.split(key, 2 * m + 1))
        pair = np.asarray(jax.random.split(key))
        # the hazard the old code sat on
        assert np.array_equal(wide[:2], pair)
        # the fixed derivation shares no key with anything split off the
        # parent directly
        fixed = np.asarray(jax.random.split(jax.random.split(key)[0],
                                            2 * m + 1))
        parent_derived = {tuple(k) for k in wide} | {tuple(k) for k in pair}
        assert all(tuple(k) not in parent_derived for k in fixed)
    finally:
        jax.config.update("jax_threefry_partitionable", was)
    # same disjointness under this build's default threefry
    key = jax.random.PRNGKey(42)
    wide = np.asarray(jax.random.split(key, 2 * m + 1))
    pair = np.asarray(jax.random.split(key))
    fixed = np.asarray(jax.random.split(jax.random.split(key)[0], 2 * m + 1))
    parent_derived = {tuple(k) for k in wide} | {tuple(k) for k in pair}
    assert all(tuple(k) not in parent_derived for k in fixed)


def test_spec_and_plain_key_chains_diverge():
    """The spec-path ``new_key`` must differ from the opt-out path's for
    the same input key — pre-fix, under partitionable threefry, they were
    the same key, so a request toggling speculation replayed its stream."""
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(2, 7)).astype(np.float32))
        drafts = jnp.asarray(np.asarray(logits).argmax(-1), jnp.int32)
        q = jnp.full((2, 7), 1.0 / 7, jnp.float32)
        args = (logits, drafts, q, jax.random.PRNGKey(5),
                jnp.float32(0.9), jnp.int32(0), jnp.float32(1.0))
        *_, k_spec = speculative_verify(*args, jnp.asarray(True))
        *_, k_plain = speculative_verify(*args, jnp.asarray(False))
        assert not np.array_equal(np.asarray(k_spec), np.asarray(k_plain))
    finally:
        jax.config.update("jax_threefry_partitionable", was)


def test_speculative_sampling_preserves_target_distribution():
    """Acceptance for the corrected sampler: with a deliberately wrong
    draft distribution q != p, the emitted-token marginal still equals the
    target p (the accept/resample identity) — measured over 4096 key
    chains with m=1."""
    n = 4096
    v = 5
    rng = np.random.default_rng(13)
    logits = jnp.asarray(rng.normal(size=(1, v)).astype(np.float32))
    temp = 1.0
    p = np.asarray(modified_probs(logits[0], jnp.float32(temp),
                                  jnp.int32(0), jnp.float32(1.0)))
    # a skewed draft distribution, nothing like p
    q = np.asarray([0.70, 0.15, 0.05, 0.05, 0.05], np.float32)
    drafts = rng.choice(v, size=(n, 1), p=q / q.sum()).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(14), n)

    verify = jax.vmap(
        speculative_verify,
        in_axes=(None, 0, None, 0, None, None, None, None))
    tokens, counts, _, _ = verify(
        logits, jnp.asarray(drafts), jnp.asarray(np.tile(q, (1, 1))),
        keys, jnp.float32(temp), jnp.int32(0), jnp.float32(1.0),
        jnp.asarray(True))
    counts = np.asarray(counts)
    assert (counts >= 1).all()  # m=1 always emits: accept or correction
    first = np.asarray(tokens)[:, 0]
    freq = np.bincount(first, minlength=v) / n
    # per-bin std is sqrt(p(1-p)/n) <= 0.008; 0.035 is > 4 sigma
    np.testing.assert_allclose(freq, p, atol=0.035)


# ------------------------------------------------------------ parity pins


def test_speculative_greedy_parity_staggered(lm, spec_engine):
    """Acceptance: greedy speculative tokens are bitwise the greedy
    reference under staggered concurrent arrival — the draft model (random
    params, so frequently wrong) only changes *when* tokens are emitted."""
    module, params = lm
    engine, _ = spec_engine
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, VOCAB, size=n).tolist() for n in (3, 7, 5)]
    steps = (8, 6, 10)
    refs = [_ref(module, params, p, s) for p, s in zip(prompts, steps)]

    pendings = []
    for p, s in zip(prompts, steps):
        pendings.append(engine.submit(GenerateRequest(
            prompt=p, max_new_tokens=s)))
        time.sleep(0.02)
    for pending, ref in zip(pendings, refs):
        result = pending.result(timeout=120)
        assert result is not None and result.tokens == ref


def test_speculative_greedy_parity_staged(lm, make_engine):
    """Same pin for StagedLM serving with a TransformerLM draft — the draft
    only needs a decode_spec, not the target's architecture."""
    module = StagedLM(vocab_size=VOCAB, dim=16, heads=2, num_stages=2,
                      blocks_per_stage=1, max_len=32)
    params, _ = module.init(jax.random.PRNGKey(3), np.zeros((1, 4), np.int32))
    dmodule = TransformerLM(vocab_size=VOCAB, dim=16, heads=2, num_layers=1,
                            max_len=32)
    dparams = dmodule.init(jax.random.PRNGKey(4),
                           np.zeros((1, 4), np.int32))["params"]
    from distkeras_tpu.models.generate import greedy_generate_staged

    engine = make_engine(module, params, num_slots=2, page_size=8,
                         draft_model=dmodule, draft_params=dparams,
                         spec_tokens=2)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, VOCAB, size=n).tolist() for n in (4, 6)]
    refs = []
    for p in prompts:
        out = greedy_generate_staged(
            module, params, np.asarray([p], np.int32), 7)
        refs.append(out[0, len(p):].tolist())
    pendings = [engine.submit(GenerateRequest(prompt=p, max_new_tokens=7))
                for p in prompts]
    for pending, ref in zip(pendings, refs):
        result = pending.result(timeout=120)
        assert result is not None and result.tokens == ref


def test_faithful_draft_steps_per_token_below_one(lm, faithful_engine):
    """Acceptance: with the draft == the target, greedy windows fully
    accept, so decode steps per generated token drop below 1 and the
    accepted/proposed counters agree."""
    module, params = lm
    engine, registry = faithful_engine

    def counters():
        snap = registry.snapshot()
        return {k: snap[f"serving_{k}"]["value"]
                for k in ("decode_steps_total", "tokens_total",
                          "spec_proposed_total", "spec_accepted_total")}

    before = counters()
    result = engine.generate([1, 2, 3], max_new_tokens=13, timeout=120)
    assert result.tokens == _ref(module, params, [1, 2, 3], 13)

    delta = {k: v - before[k] for k, v in counters().items()}
    assert delta["tokens_total"] == 13
    assert delta["decode_steps_total"] / 13 < 1, delta
    # faithful: no rejections
    assert delta["spec_proposed_total"] > 0
    assert delta["spec_accepted_total"] == delta["spec_proposed_total"]


def test_speculative_stochastic_determinism_and_optout(spec_engine,
                                                       plain_engine):
    """Stochastic speculative sampling is exact: (a) same seed -> same
    tokens across different co-batched traffic; (b) a request opting OUT on
    a speculative engine reproduces the plain engine's tokens bitwise (the
    opt-out path consumes the identical key chain)."""
    engine, _ = spec_engine
    knobs = dict(max_new_tokens=9, temperature=0.9, top_k=7, top_p=0.95,
                 seed=123)

    solo = engine.generate([2, 3, 4], timeout=120, **knobs)
    # same request with neighbours (one speculative, one opted out)
    rng = np.random.default_rng(6)
    others = [
        engine.submit(GenerateRequest(
            prompt=rng.integers(0, VOCAB, size=5).tolist(),
            max_new_tokens=8, temperature=0.7, seed=9)),
        engine.submit(GenerateRequest(
            prompt=rng.integers(0, VOCAB, size=4).tolist(),
            max_new_tokens=8, temperature=0.7, seed=10, speculative=False)),
    ]
    busy = engine.generate([2, 3, 4], timeout=120, **knobs)
    assert busy.tokens == solo.tokens
    assert all(p.result(timeout=120) is not None for p in others)

    plain, _ = plain_engine
    baseline = plain.generate([2, 3, 4], timeout=120, **knobs)
    optout = engine.generate([2, 3, 4], timeout=120, speculative=False,
                             **knobs)
    assert optout.tokens == baseline.tokens


def test_speculative_rejects_without_draft(plain_engine):
    engine, _ = plain_engine
    with pytest.raises(ValueError, match="draft_model"):
        engine.submit(GenerateRequest(prompt=[1, 2], speculative=True))


# -------------------------------------------------------------- bucketing


def test_prefill_bucket_ladder_and_validation(lm, plain_engine,
                                              make_engine):
    module, params = lm
    engine, _ = plain_engine
    assert engine.prefill_buckets == (8, 16, 32)
    custom = make_engine(module, params, num_slots=2, page_size=8,
                         prefill_buckets=[8])
    assert custom.prefill_buckets == (8, 32)  # max_context always appended
    with pytest.raises(ValueError, match="multiple"):
        make_engine(module, params, num_slots=2, page_size=8,
                    prefill_buckets=[12])
    with pytest.raises(ValueError, match="multiple"):
        make_engine(module, params, num_slots=2, page_size=8,
                    prefill_buckets=[64])


def test_prefill_padding_counter_drops_vs_single_bucket(lm, plain_engine,
                                                        make_engine):
    """Acceptance: the padded-tokens counter shows bucketing beating the
    single pad-to-max-context prefill on short prompts."""
    module, params = lm
    prompts = [[1, 2, 3], list(range(1, 6)), list(range(1, 11))]

    bucketed, bucketed_reg = plain_engine
    single_reg = Registry()
    single = make_engine(module, params, num_slots=2, page_size=8,
                         registry=single_reg, prefill_buckets=[32])
    before = bucketed_reg.snapshot()["serving_prefill_padded_tokens"]["value"]
    for p in prompts:
        a = bucketed.generate(p, max_new_tokens=4, timeout=120)
        b = single.generate(p, max_new_tokens=4, timeout=120)
        assert a.tokens == b.tokens  # padding is FLOPs, never values

    padded = (bucketed_reg.snapshot()["serving_prefill_padded_tokens"]["value"]
              - before)
    baseline = single_reg.snapshot()["serving_prefill_padded_tokens"]["value"]
    # buckets 8/8/16 vs 32/32/32
    assert padded == sum(w - len(p) for w, p in zip((8, 8, 16), prompts))
    assert baseline == sum(32 - len(p) for p in prompts)
    assert padded < baseline


def test_speculative_engine_compile_pin(spec_engine):
    """Acceptance: a speculative engine holds the compile-count pin too —
    after warming the used buckets, admissions/retirements/bucket hits and
    speculative traffic add ZERO compiles (draft step + verify are one
    program each)."""
    engine, _ = spec_engine
    install_jax_hooks()
    probe = jax.jit(lambda x: x + 2)
    probe(np.ones(2))
    engine.generate([1, 2, 3], max_new_tokens=4, timeout=120)
    engine.generate(list(range(1, 11)), max_new_tokens=4, timeout=120)

    base = telemetry.metrics.snapshot()["jax_compiles_total"]["value"]
    rng = np.random.default_rng(7)
    pendings = []
    for i, n in enumerate((2, 9, 5, 12)):
        pendings.append(engine.submit(GenerateRequest(
            prompt=rng.integers(0, VOCAB, size=n).tolist(),
            max_new_tokens=4 + i,
            temperature=0.0 if i % 2 else 0.8,
            seed=i,
            speculative=(None if i != 1 else False),
        )))
        time.sleep(0.01)
    assert all(p.result(timeout=120) is not None for p in pendings)
    after = telemetry.metrics.snapshot()["jax_compiles_total"]["value"]
    assert after == base, f"{after - base} recompiles after warmup"


# ------------------------------------------------------------ sharded decode


def test_sharded_decode_token_parity_and_speculative_smoke(make_engine):
    """The tensor-parallel engine on the 8-device CPU mesh serves the same
    greedy tokens as the unsharded greedy reference (token-equal; the psum
    reorders float sums, so bitwise equality across mesh configs is not
    claimed) — and sharded verify + replicated draft compose: greedy
    speculative on the mesh matches the mesh's own non-speculative stream
    bitwise."""
    module = TransformerLM(vocab_size=VOCAB, dim=32, heads=8, num_layers=2,
                           max_len=32)
    params = module.init(jax.random.PRNGKey(8),
                         np.zeros((1, 4), np.int32))["params"]
    mesh = make_mesh(8, axis_name="model")
    sharded = make_engine(module, params, num_slots=2, page_size=8,
                          mesh=mesh)

    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, VOCAB, size=n).tolist() for n in (3, 6)]
    mesh_tokens = []
    for p in prompts:
        a = sharded.generate(p, max_new_tokens=6, timeout=120)
        assert a.tokens == _ref(module, params, p, 6)
        mesh_tokens.append(a.tokens)

    dmodule = TransformerLM(vocab_size=VOCAB, dim=32, heads=8, num_layers=1,
                            max_len=32)
    dparams = dmodule.init(jax.random.PRNGKey(11),
                           np.zeros((1, 4), np.int32))["params"]
    spec = make_engine(module, params, num_slots=2, page_size=8, mesh=mesh,
                       draft_model=dmodule, draft_params=dparams,
                       spec_tokens=2)
    for p, want in zip(prompts, mesh_tokens):
        got = spec.generate(p, max_new_tokens=6, timeout=120)
        assert got.tokens == want


def test_sharded_engine_validates_mesh(lm, make_engine):
    module, params = lm  # heads=2, not divisible by 8
    with pytest.raises(ValueError, match="divisible"):
        make_engine(module, params, mesh=make_mesh(8, axis_name="model"))


# -------------------------------------------------------- cache churn


def test_paged_cache_churn_never_leaks(lm, faithful_engine):
    """Alloc/free churn across interleaved admissions: after every request
    retires, the free list is whole, tables are all-scratch, and a
    max-context request still fits (``max_context`` stays honest).  Runs on
    a speculative engine so the churn exercises the multi-token
    append/rollback paths."""
    module, params = lm
    engine, _ = faithful_engine
    cache = engine._cache
    total_free = cache.pages_free
    rng = np.random.default_rng(12)
    for round_ix in range(4):
        sizes = rng.integers(2, 14, size=5)
        pendings = [
            engine.submit(GenerateRequest(
                prompt=rng.integers(0, VOCAB, size=int(n)).tolist(),
                max_new_tokens=int(rng.integers(1, 8)),
                seed=round_ix * 10 + i,
                speculative=bool(i % 2 == 0),
            ))
            for i, n in enumerate(sizes)
        ]
        assert all(p.result(timeout=120) is not None for p in pendings)
    assert engine._queue.pop() is None
    assert cache.pages_free == total_free, "page leak under churn"
    assert (cache.tables == 0).all()
    # capacity honest after churn: a request needing every page of one slot
    long_prompt = [i % VOCAB for i in range(25)]
    big = engine.generate(long_prompt, max_new_tokens=6, timeout=120)
    assert big.tokens == _ref(module, params, long_prompt, 6)
    assert cache.pages_free == total_free


def test_append_and_rollback_rows_respect_tables():
    """Unit pin for the traced helpers: rows land in the owning slot's
    pages at the right offsets, rejected suffixes are zeroed, and overhang
    past capacity is absorbed by the scratch page."""
    cache = PagedKVCache(num_layers=1, num_slots=2, page_size=4,
                         pages_per_slot=2, heads=1, head_dim=1)
    cache.alloc(0, 2)
    cache.alloc(1, 2)
    tables = jnp.asarray(cache.tables)
    pool = cache.k_pages[0]  # one layer: zeros [pages, 4, 1]

    rows = jnp.arange(1, 7, dtype=pool.dtype).reshape(2, 3, 1, 1)
    pos = jnp.asarray([3, 6], jnp.int32)  # slot1: rows 6,7 valid, 8 overhangs
    pool = append_rows(pool, tables, pos, rows)
    got = np.asarray(pool)
    t = cache.tables
    assert got[t[0, 0], 3, 0] == 1          # slot0 logical 3
    assert got[t[0, 1], 0, 0] == 2          # slot0 logical 4 -> page 2
    assert got[t[0, 1], 1, 0] == 3
    assert got[t[1, 1], 2, 0] == 4          # slot1 logical 6 (table row 1)
    assert got[t[1, 1], 3, 0] == 5
    # logical 8 == capacity: redirected to scratch, owned pages untouched
    assert 6 not in got[t[0]] and 6 not in got[t[1, 1]]

    # rollback: slot0 keeps 1 of 3 rows, slot1 keeps all (count >= m)
    pool = rollback_rows(pool, tables, pos, jnp.asarray([1, 3]), 3)
    got = np.asarray(pool)
    assert got[t[0, 0], 3, 0] == 1          # kept
    assert got[t[0, 1], 0, 0] == 0          # rejected -> zeroed
    assert got[t[0, 1], 1, 0] == 0
    assert got[t[1, 1], 2, 0] == 4          # other slot untouched
    assert got[t[1, 1], 3, 0] == 5
