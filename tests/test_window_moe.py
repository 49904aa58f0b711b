"""``WindowMoELM`` (sliding-window and global grouped-query layers over
expert layers) at a small size on the CPU, in float32 on seeded weights,
against the benchmark's plain reference (``benchmark/reference_window_moe.py``,
one file, loaded by path as a run loads it): the full forward, four near
variants of the attention told apart from it, the chip's share tied to the
uncut expert layer, the weights' layout, and the block's counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import WindowMoELM
from distkeras_tpu.models.window_moe import attention_pairs

#: hidden 64, 8 query heads over 2 (global) or 4 (sliding) key-value heads,
#: query and key width 24 (8 of them rotated), value width 16, a window of 8,
#: 16 experts top-4; layers: global dense, two sliding, global, sliding
TINY = dict(
    vocab_size=97, max_len=64, hidden_size=64, num_hidden_layers=5,
    num_attention_heads=8, num_key_value_heads=2, head_dim=24, v_head_dim=16,
    swa_num_attention_heads=8, swa_num_key_value_heads=4, swa_head_dim=24,
    swa_v_head_dim=16, hybrid_layer_pattern=[0, 1, 1, 0, 1],
    moe_layer_freq=[0, 1, 1, 1, 1], sliding_window=8,
    partial_rotary_factor=0.334, rope_theta=1e7, swa_rope_theta=1e4,
    add_full_attention_sink_bias=False, add_swa_attention_sink_bias=True,
    attention_value_scale=0.707, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
    routed_scaling_factor=None, layernorm_epsilon=1e-5)

_LOADED = {}


@pytest.fixture
def reference(harness):
    """One instance for the session: its jitted pieces compile once."""
    if not _LOADED:
        _LOADED["module"] = harness.load_module(".", "reference_window_moe")
    return _LOADED["module"]


def _tokens(seed, length):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], length)


@pytest.mark.parametrize("held", [None, [4, 4]])
def test_full_forward_equals_the_reference(reference, held):
    """Every layer's equations, and the same share of the experts, over 40
    positions (five windows).  The tolerance is float32's: both sides
    compute in float32 on the CPU and differ by the order of their sums
    alone (logits of order 4)."""
    sizes = dict(TINY, held_experts=held)
    model = WindowMoELM(**sizes)
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(1, 40)
    reference.configure(**sizes)
    want = reference._reference_logits(params, jnp.asarray(tokens))
    got = model(params, tokens[None])[0]
    assert got.shape == (40, TINY["vocab_size"])
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("broken", [
    {"add_swa_attention_sink_bias": False},            # the sink dropped
    {"sliding_window": TINY["max_len"]},                # whole-context reads
    {"rope_theta": 1e4, "swa_rope_theta": 1e7},         # the thetas swapped
    {"partial_rotary_factor": 1.0}],                    # all 24 rotated
    ids=["no_sink", "whole_context", "thetas_swapped", "full_rotary"])
def test_a_near_variant_of_the_attention_fails_the_comparison(
        reference, broken):
    """The same weights through a model that drops the sinks, reads the
    whole context in its sliding layers, swaps the two rotary bases or
    rotates every dimension: its logits lie a thousand times the
    comparison's tolerance from the reference's."""
    params = WindowMoELM(**TINY).init(jax.random.PRNGKey(0))
    tokens = _tokens(1, 40)
    reference.configure(**TINY)
    want = reference._reference_logits(params, jnp.asarray(tokens))
    got = WindowMoELM(**dict(TINY, **broken))(params, tokens[None])[0]
    assert float(jnp.abs(got - want).max()) > 3e-2


def test_the_reference_and_the_model_lay_their_weights_out_alike(reference):
    sizes = dict(TINY, held_experts=[4, 4])
    ours = jax.tree.map(lambda a: (a.shape, a.dtype), WindowMoELM(
        **sizes).init(jax.random.PRNGKey(0), jnp.bfloat16))
    theirs = jax.tree.map(lambda a: (a.shape, a.dtype),
                          reference.make_weights(7, **sizes))
    assert ours == theirs
    first, sliding = ours["layers"][0], ours["layers"][1]
    assert sorted(first) == ["attn_norm", "down", "ffn_norm", "gate", "k",
                             "o", "q", "up", "v"]
    assert first["k"] == ((64, 2, 24), jnp.bfloat16)
    assert sliding["k"] == ((64, 4, 24), jnp.bfloat16)
    assert sliding["v"] == ((64, 4, 16), jnp.bfloat16)
    assert sliding["sink"] == ((8,), jnp.float32)       # one a head
    assert sliding["router"] == ((64, 16), jnp.float32)  # all 16 outputs
    assert sliding["experts_gate"] == ((4, 64, 32), jnp.bfloat16)  # the held
    assert "sink" not in ours["layers"][3]  # a global layer has none


def _unit_rows(key, tokens):
    h = jax.random.normal(key, (tokens, TINY["hidden_size"]))
    return h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """The share ties to the model: the held terms of four shares that
    partition the 16 experts add up to the reference's uncut expert layer,
    and the uncut program's layer says the same."""
    p = WindowMoELM(**TINY).init(jax.random.PRNGKey(3))["layers"][2]
    u = _unit_rows(jax.random.PRNGKey(4), 24)
    total = 0.0
    for first in (0, 4, 8, 12):
        part = dict(p, **{name: p[name][first:first + 4] for name in (
            "experts_gate", "experts_up", "experts_down")})
        model = WindowMoELM(**dict(TINY, held_experts=[first, 4]))
        total = total + model.feed_forward(part, u)[0]
    want = reference._experts(p, u, reference._frozen(TINY), "float32")
    np.testing.assert_allclose(total, want, atol=2e-5)
    np.testing.assert_allclose(WindowMoELM(**TINY).feed_forward(p, u)[0],
                               want, atol=2e-5)


def test_the_layers_kinds_and_the_declared_state():
    """Global layers page ``k`` and ``v`` of their key-value heads, sliding
    ones keep ``sliding_k`` and ``sliding_v`` in a ring of the window; the
    family's rule fills the pattern where none is given."""
    spec = WindowMoELM(**TINY).decode_spec(None)
    assert spec.state == (("k", 48), ("v", 32), ("sliding_k", 96),
                          ("sliding_v", 64))
    assert spec.layer_kinds == (("k", "v"), ("sliding_k", "sliding_v"),
                                ("sliding_k", "sliding_v"), ("k", "v"),
                                ("sliding_k", "sliding_v"))
    assert spec.sliding == (("sliding_k", 8), ("sliding_v", 8))
    assert spec.window is None and spec.shard is None
    published = WindowMoELM(vocab_size=8, max_len=8)
    pattern = published.hybrid_layer_pattern
    assert len(pattern) == 48 and pattern.count(0) == 9
    assert [i for i, kind in enumerate(pattern) if kind == 0] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert published.moe_layer_freq == (0,) + (1,) * 47
    assert published.rotary_dim(True) == published.rotary_dim(False) == 64


@pytest.mark.parametrize("rows,window,want", [
    (8, None, 64), (8, 8, 64),           # one block: the whole square
    (1024, None, 512 * 512 * 3),         # two blocks: 512 and 1024 keys
    (1024, 128, 512 * 512 + 512 * 639),  # the second block's band: 639 keys
    (8192, None, 512 * 512 * 136)])      # sixteen blocks: 1 + 2 + ... + 16
def test_the_prefills_pairs_by_hand(rows, window, want):
    assert attention_pairs(rows, window) == want


def test_the_blocks_counters_take_what_the_programs_hand_back():
    """``observe`` on hand-made ``aux``: a decode step of 3 live slots at
    positions 4, 9 and 20 through the five layers (three sliding), whose
    first layer hands back the live slots and their positions' sum, then a
    prefill of width 16, whose first layer hands back its width."""
    from distkeras_tpu.telemetry.metrics import Registry

    spec = WindowMoELM(**dict(TINY, held_experts=[4, 4])).decode_spec(None)
    registry = Registry()
    instruments = spec.instruments(registry)
    walk = (np.asarray([3, 0, 1, 0], np.int32), np.int32(2), np.int32(2))
    live = 5 + 10 + 21
    totals = np.asarray([3, live], np.int32)
    step = ({"sliding": totals},) + ({"walk": walk},) * 4
    spec.observe(instruments, step, 3, True)
    value = lambda name: registry.snapshot()[name]["value"]
    assert value("serving_sliding_positions_read_total") == 72
    assert value("serving_sliding_positions_live_total") == 108
    assert value("serving_moe_assignments_total") == 3 * 4 * 4
    assert value("serving_moe_assignments_held_total") == 4 * 4
    prefill = ({"width": np.int32(16)},) + ({"walk": walk},) * 4
    spec.observe(instruments, prefill, 12, False)
    assert value("serving_prefill_attn_pairs_total") == 5 * 256
    assert value("serving_prefill_attn_causal_pairs_total") == 5 * 136
    assert value("serving_sliding_positions_read_total") == 72  # steps only


def test_the_counters_of_the_uncut_depth_do_not_overflow():
    """At the published 48 layers a 12,288-wide prefill's causal pairs pass
    2**31, and so do a long-context step's positions over 39 sliding
    layers: the host counts them in Python ints."""
    from distkeras_tpu.telemetry.metrics import Registry

    spec = WindowMoELM(vocab_size=8, max_len=13312).decode_spec(None)
    registry = Registry()
    instruments = spec.instruments(registry)
    value = lambda name: registry.snapshot()[name]["value"]
    spec.observe(instruments, ({"width": np.int32(12288)},), 12288, False)
    assert value("serving_prefill_attn_causal_pairs_total") == (
        48 * 12288 * 12289 // 2) > 2 ** 31
    assert value("serving_prefill_attn_pairs_total") == (
        9 * attention_pairs(12288) + 39 * attention_pairs(12288, 128))
    spec.observe(instruments, ({"sliding": np.asarray(
        [4096, 4096 * 13312], np.int32)},), 4096, True)
    assert value("serving_sliding_positions_live_total") == 39 * 4096 * 13312


def test_a_sink_in_the_global_layers_is_refused():
    """The global layers' step reads its pages with no sink, so a model
    that asked for one would prefill and decode two different models."""
    with pytest.raises(ValueError, match="add_full_attention_sink_bias"):
        WindowMoELM(**dict(TINY, add_full_attention_sink_bias=True))
