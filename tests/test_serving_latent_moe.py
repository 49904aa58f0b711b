"""``LatentMoELM`` through ``ServingEngine``: the block contract serves a
second kind of block by the same loop and the same programs' builders.  At a
small size on the CPU in float32, prefill and then decoding through the
latent pages agree with the plain reference's full-context pass at every
generated position (the served token is the reference's best there), over
slots of different lengths admitted and retired mid-flight and across page
boundaries; the block's counters add up; the engine refuses the builds the
block does not bring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import LatentMoELM, TransformerLM
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.serving import GenerateRequest, ServingEngine
from distkeras_tpu.telemetry.metrics import Registry

from test_latent_moe import TINY
from test_latent_moe import reference as _reference  # noqa: F401 (fixture)

HELD = [2, 4]  # four of the eight experts: a chip's share
SIZES = dict(TINY, held_experts=HELD)


@pytest.fixture
def reference(_reference):
    _reference.configure(**SIZES)
    return _reference


@pytest.fixture(scope="module")
def lm():
    model = LatentMoELM(**SIZES)
    return model, model.init(jax.random.PRNGKey(11))


def _prompt(seed, length):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], length).tolist()


def _counter(registry, name):
    return registry.snapshot()[name]["value"]


def test_prefill_then_decode_through_the_pages_equals_the_reference(
        lm, reference):
    """(b) five requests on two slots, so that slots are given back and
    taken mid-flight; prompts of 5 to 33 tokens on pages of 8, so that
    prefill chunks and decode steps cross page boundaries (and the second
    block of pages at position 16 of a 3-page bucket)."""
    model, params = lm
    registry = Registry()
    engine = ServingEngine(model, params, num_slots=2, page_size=8,
                           registry=registry)
    mix = [(5, 20), (17, 9), (33, 14), (8, 30), (24, 3)]
    try:
        pending = [engine.submit(GenerateRequest(
            prompt=_prompt(plen, plen), max_new_tokens=new, temperature=0.0))
            for plen, new in mix]
        results = [p.result(timeout=600) for p in pending]
        assert engine._decode._cache_size() == 1  # one program, never retraced
    finally:
        engine.stop()
    for (plen, new), result in zip(mix, results):
        assert result.finish_reason == "length" and len(result.tokens) == new
        gaps = reference.served_gaps(params, _prompt(plen, plen),
                                     result.tokens, TINY["max_len"])
        # the served token is the full-context reference's best, everywhere
        assert float(gaps.max()) <= 1e-5, (plen, new, gaps)
    # the block's counters: every live token of every prefill and step,
    # two experts each, in the two expert layers
    live = sum(plen + new - 1 for plen, new in mix)
    assert _counter(registry, "serving_moe_assignments_total") == live * 2 * 2
    held = _counter(registry, "serving_moe_assignments_held_total")
    assert 0 < held < live * 2 * 2
    load = registry.snapshot()["serving_moe_expert_load_max_over_mean"]
    assert load["count"] > 0 and load["sum"] / load["count"] >= 1.0
    # three layers of one 24-wide float32 row
    assert _counter(registry, "serving_state_per_position_bytes") == 3 * 24 * 4
    assert _counter(registry, "serving_kv_pages_in_use") == 0


def test_held_counts_match_the_routing_of_the_reference(lm, reference):
    """The counters against the reference's own routing of the same
    sequence: assignments of the fed tokens that met experts 2..5."""
    model, params = lm
    registry = Registry()
    engine = ServingEngine(model, params, num_slots=1, page_size=8,
                           registry=registry)
    prompt = _prompt(3, 12)
    try:
        served = engine.generate(prompt, max_new_tokens=10, timeout=600).tokens
    finally:
        engine.stop()
    fed = jnp.asarray((prompt + served)[:-1])
    x = params["embed"][fed]
    want = 0
    frozen = reference._frozen(SIZES)
    for p in params["layers"]:
        if "router" in p:
            attended = x + reference._attention(
                p, x, SIZES, *reference._arithmetic("float32"))
            ids, _ = model.route(p, np.asarray(
                attended * jax.lax.rsqrt(jnp.mean(
                    attended * attended, -1, keepdims=True) + 1e-6)
                * p["ffn_norm"]))
            want += int(((ids >= HELD[0]) & (ids < sum(HELD))).sum())
        x = reference._layer(p, x, frozen, "float32")
    assert _counter(registry, "serving_moe_assignments_held_total") == want


def test_bfloat16_pools_and_a_hot_swap_of_the_same_geometry(lm):
    model, params = lm
    other = model.init(jax.random.PRNGKey(12))
    engine = ServingEngine(model, params, num_slots=2, page_size=8,
                           registry=Registry(), dtype="bfloat16")
    try:
        assert engine._cache.latent_pages[0].dtype == jnp.bfloat16
        assert engine._cache.state == (("latent", 24),)
        before = engine.generate(_prompt(1, 9), max_new_tokens=6, timeout=600)
        engine.hot_swap(model, other, timeout=60)
        after = engine.generate(_prompt(1, 9), max_new_tokens=6, timeout=600)
        assert before.tokens != after.tokens
        assert engine._decode._cache_size() == 1
        wider = LatentMoELM(**dict(SIZES, kv_lora_rank=32))
        with pytest.raises(ValueError, match="geometry"):
            engine.hot_swap(wider, wider.init(jax.random.PRNGKey(0)))
        gpt = TransformerLM(vocab_size=TINY["vocab_size"], dim=16, heads=2,
                            num_layers=3, max_len=64)
        with pytest.raises(ValueError, match="geometry"):
            engine.hot_swap(gpt, gpt.init(
                jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))["params"])
    finally:
        engine.stop()


@pytest.mark.parametrize("build", ["mesh", "draft_model"])
def test_the_engine_refuses_the_builds_the_block_does_not_bring(lm, build):
    """(f) at construction, with an error that names the argument."""
    model, params = lm
    if build == "mesh":
        kwargs = {"mesh": make_mesh(2, axis_name="model")}
    else:
        kwargs = {"draft_model": model, "draft_params": params}
    with pytest.raises(ValueError, match=build + "="):
        ServingEngine(model, params, num_slots=2, page_size=8,
                      registry=Registry(), **kwargs)


def test_the_block_serves_as_a_draft_of_another_block(lm):
    """Any block is a draft (prefill and step are all a draft needs): a
    GPT-2-shaped target verifies this block's proposals, and greedy output
    is the target's own."""
    model, params = lm
    gpt = TransformerLM(vocab_size=TINY["vocab_size"], dim=16, heads=2,
                        num_layers=1, max_len=64)
    gparams = gpt.init(jax.random.PRNGKey(2),
                       np.zeros((1, 4), np.int32))["params"]
    plain = ServingEngine(gpt, gparams, num_slots=2, page_size=8,
                          registry=Registry())
    spec = ServingEngine(gpt, gparams, num_slots=2, page_size=8,
                         registry=Registry(), draft_model=model,
                         draft_params=params, spec_tokens=3)
    try:
        prompt = _prompt(4, 7)
        want = plain.generate(prompt, max_new_tokens=9, timeout=600).tokens
        got = spec.generate(prompt, max_new_tokens=9, timeout=600).tokens
        assert got == want
        assert spec._draft_cache.state == (("latent", 24),)
    finally:
        plain.stop()
        spec.stop()


def test_the_state_gauge_reads_the_gpt2_block_too():
    gpt = TransformerLM(vocab_size=31, dim=16, heads=2, num_layers=2,
                        max_len=32)
    params = gpt.init(jax.random.PRNGKey(0),
                      np.zeros((1, 4), np.int32))["params"]
    registry = Registry()
    engine = ServingEngine(gpt, params, num_slots=2, page_size=8,
                           registry=registry)
    try:
        assert engine._cache.state == (("k", 16), ("v", 16))
        assert _counter(registry, "serving_state_per_position_bytes") == (
            2 * 2 * 16 * 4)
        assert not any("moe" in name for name in registry.snapshot())
    finally:
        engine.stop()
