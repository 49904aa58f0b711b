"""``ShortcutMoELM`` through ``ServingEngine``: a block with **two pools of
the same kind a layer** served by the same loop and the same programs'
builders.  At a small size on the CPU in float32, prefill and then decoding
through both latent pools of every double layer agree with the plain
reference's full-context pass at every generated position (logits by the
block's own ``DecodeSpec`` functions over a paged cache; the engine's served
tokens by the reference's gaps), over slots admitted and retired mid-flight
and across page boundaries; the block's counters add up three ways (held,
absent, zero-compute); the engine refuses the builds the block does not
bring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import LatentMoELM, ShortcutMoELM
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.serving import GenerateRequest, ServingEngine
from distkeras_tpu.serving.cache import PagedKVCache, fit_rows
from distkeras_tpu.telemetry.metrics import Registry

from test_scmoe import TINY
from test_scmoe import reference as _reference  # noqa: F401 (fixture)

HELD = [2, 4]  # four of the eight routed experts: a chip's share
SIZES = dict(TINY, held_experts=HELD)
K, LAYERS = TINY["moe_topk"], TINY["num_layers"]


@pytest.fixture
def reference(_reference):
    _reference.configure(**SIZES)
    return _reference


@pytest.fixture(scope="module")
def lm():
    model = ShortcutMoELM(**SIZES)
    return model, model.init(jax.random.PRNGKey(11))


def _prompt(seed, length):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], length).tolist()


def _counter(registry, name):
    return registry.snapshot()[name]["value"]


def _routing(reference, params, fed):
    """The reference's own picks ``[layers, tokens, k]`` for the sequence
    ``fed``: each double layer routes from its first sub-block's normed
    stream."""
    frozen, eps = reference._frozen(SIZES), SIZES["rms_norm_eps"]
    x = params["embed"][jnp.asarray(fed)]
    picks = []
    for p in params["layers"]:
        first = p["blocks"][0]
        x1 = x + reference._attention(
            first, reference._normed(x, first["attn_norm"], eps), frozen,
            "float32")
        u = reference._normed(x1, first["ffn_norm"], eps)
        scores = jax.nn.softmax(u @ p["router"], axis=-1)
        picks.append(np.asarray(
            jax.lax.top_k(scores + p["router_bias"], K)[1]))
        x = reference._layer(p, x, frozen, "float32")
    return np.stack(picks)


def test_prefill_then_decode_through_two_pools_equals_the_reference_logits(
        lm, reference):
    """The block's own ``prefill`` and ``step`` over a paged cache with two
    pools a layer, teacher-forced: the logits of the prompt's last row and of
    every decoded position against the reference's full forward.  A prompt
    of 13 on pages of 8 in a table in reverse order, 12 steps across two
    page boundaries; tolerance float32's (logits of order 4)."""
    model, params = lm
    spec = model.decode_spec(params)
    page, pages, plen, steps = 8, 4, 13, 12
    cache = PagedKVCache(num_layers=spec.num_layers, num_slots=1,
                         page_size=page, pages_per_slot=pages,
                         state=spec.state)
    assert sorted(cache.pools) == ["latent_0", "latent_1"]
    table = jnp.arange(pages, 0, -1, dtype=jnp.int32)
    sequence = _prompt(7, plen + steps)
    want = reference._reference_logits(params, jnp.asarray(sequence))
    pools = {name: list(layers) for name, layers in cache.pools.items()}
    width = 16  # the prompt's chunk, padded to whole pages
    chunk = jnp.zeros((1, width), jnp.int32).at[0, :plen].set(
        jnp.asarray(sequence[:plen]))
    positions = jnp.arange(width)[None]
    x = spec.embed(params, chunk, positions)
    for li in range(spec.num_layers):
        def write(name, rows, li=li):
            pool = pools[name][li]
            pools[name][li] = pool.at[table[:width // page]].set(
                fit_rows(rows.reshape(width // page, page, -1), pool))
        x, _ = spec.prefill(params, li, x, positions, write, positions < plen)
    np.testing.assert_allclose(spec.head(params, x, at=plen - 1),
                               want[plen - 1], atol=3e-5)
    for at in range(plen, plen + steps):
        pos = jnp.asarray([at], jnp.int32)
        x = spec.embed(params, jnp.asarray([[sequence[at]]]), pos[:, None])
        for li in range(spec.num_layers):
            layer, x, _ = spec.step(
                params, li, x, {n: pools[n][li] for n in pools},
                table[None], pos, jnp.ones((1, 1), bool))
            for name in pools:
                pools[name][li] = layer[name]
        np.testing.assert_allclose(spec.head(params, x)[0, 0], want[at],
                                   atol=3e-5)
    # the two sub-blocks keep different rows: neither pool stands in for
    # the other
    apart = jnp.abs(pools["latent_0"][0] - pools["latent_1"][0])
    assert float(apart.max()) > 0.1


def test_the_engine_serves_it_and_the_counters_add_up_three_ways(
        lm, reference):
    """Five requests on two slots, so that slots are given back and taken
    mid-flight; prompts of 5 to 33 tokens on pages of 8.  The served token
    is the full-context reference's best everywhere; held + absent + zero
    assignments are ``live tokens x 3 x 2 layers``, each as the reference's
    own routing of the same sequences has it."""
    model, params = lm
    registry = Registry()
    engine = ServingEngine(model, params, num_slots=2, page_size=8,
                           registry=registry)
    mix = [(5, 20), (17, 9), (33, 14), (8, 30), (24, 3)]
    try:
        assert engine._cache.state == (("latent_0", 24), ("latent_1", 24))
        pending = [engine.submit(GenerateRequest(
            prompt=_prompt(plen, plen), max_new_tokens=new, temperature=0.0))
            for plen, new in mix]
        results = [p.result(timeout=600) for p in pending]
        assert engine._decode._cache_size() == 1  # one program, never retraced
    finally:
        engine.stop()
    held = zero = 0
    for (plen, new), result in zip(mix, results):
        assert result.finish_reason == "length" and len(result.tokens) == new
        prompt = _prompt(plen, plen)
        gaps = reference.served_gaps(params, prompt, result.tokens,
                                     TINY["max_len"])
        assert float(gaps.max()) <= 1e-5, (plen, new, gaps)
        picks = _routing(reference, params, (prompt + result.tokens)[:-1])
        held += int(((picks >= HELD[0]) & (picks < sum(HELD))).sum())
        zero += int((picks >= TINY["n_routed_experts"]).sum())
    live = sum(plen + new - 1 for plen, new in mix)
    total = live * K * LAYERS
    assert _counter(registry, "serving_moe_assignments_total") == total
    assert _counter(registry, "serving_moe_assignments_held_total") == held
    assert _counter(registry, "serving_moe_assignments_zero_total") == zero
    absent = total - held - zero  # routed experts that lie on other chips
    assert 0 < held and 0 < zero and 0 < absent
    real = registry.snapshot()["serving_moe_real_picks_max_over_mean"]
    assert real["count"] > 0
    assert 1.0 <= real["sum"] / real["count"] <= K
    load = registry.snapshot()["serving_moe_expert_load_max_over_mean"]
    assert load["count"] == 0 or load["sum"] / load["count"] >= 1.0
    assert _counter(registry, "serving_moe_tiles_total") >= _counter(
        registry, "serving_moe_experts_touched_total") > 0
    # two double layers of two 24-wide float32 rows
    assert _counter(registry, "serving_state_per_position_bytes") == (
        LAYERS * 2 * 24 * 4)
    assert _counter(registry, "serving_kv_pages_in_use") == 0


def test_bfloat16_pools_and_a_hot_swap_of_the_same_geometry(lm):
    model, params = lm
    other = model.init(jax.random.PRNGKey(12))
    engine = ServingEngine(model, params, num_slots=2, page_size=8,
                           registry=Registry(), dtype="bfloat16")
    try:
        for name in ("latent_0", "latent_1"):
            pools = engine._cache.pools[name]
            assert len(pools) == LAYERS and pools[0].dtype == jnp.bfloat16
        assert engine._cache.latent_1_pages[1].shape[-1] == 24
        before = engine.generate(_prompt(1, 9), max_new_tokens=6, timeout=600)
        engine.hot_swap(model, other, timeout=60)
        after = engine.generate(_prompt(1, 9), max_new_tokens=6, timeout=600)
        assert before.tokens != after.tokens
        assert engine._decode._cache_size() == 1
        fewer = ShortcutMoELM(**dict(SIZES, zero_expert_num=2))
        with pytest.raises(ValueError, match="geometry"):
            engine.hot_swap(fewer, fewer.init(jax.random.PRNGKey(0)))
        # the other latent block keeps one pool a layer: another state
        single = LatentMoELM(
            vocab_size=TINY["vocab_size"], max_len=64, hidden_size=64,
            num_hidden_layers=2, num_attention_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_tok=2)
        with pytest.raises(ValueError, match="geometry"):
            engine.hot_swap(single, single.init(jax.random.PRNGKey(0)))
    finally:
        engine.stop()


@pytest.mark.parametrize("build", ["mesh", "draft_model"])
def test_the_engine_refuses_the_builds_the_block_does_not_bring(lm, build):
    """At construction, with an error that names the argument."""
    model, params = lm
    if build == "mesh":
        kwargs = {"mesh": make_mesh(2, axis_name="model")}
    else:
        kwargs = {"draft_model": model, "draft_params": params}
    with pytest.raises(ValueError, match=build + "="):
        ServingEngine(model, params, num_slots=2, page_size=8,
                      registry=Registry(), **kwargs)
