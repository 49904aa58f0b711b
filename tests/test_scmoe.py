"""``ShortcutMoELM`` (double layers with a shortcut expert branch and
zero-compute experts) at a small size on the CPU, in float32 on seeded
weights, against the benchmark's plain reference
(``benchmark/reference_scmoe.py``, one file, loaded by path as a run loads
it): the full forward, the chip's share tied to the uncut layer, the
shortcut pinned against two near variants, the zero-compute term, picking
against weighing, the LoRA scales, and the walk at this router's width."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import ShortcutMoELM
from distkeras_tpu.models.latent_moe import gated, rms_norm, tile_height

#: hidden 64, 4 heads, latent 16, query latent 24, rope 8, 8 routed + 4
#: zero-compute experts top-3, 2 double layers
TINY = dict(
    vocab_size=97, max_len=64, hidden_size=64, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8, v_head_dim=8,
    qk_nope_head_dim=8, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6.0, n_routed_experts=8, zero_expert_num=4,
    moe_topk=3, rms_norm_eps=1e-5, rope_theta=1e7)

_LOADED = {}


@pytest.fixture
def reference(harness):
    """One instance for the file: its jitted pieces compile once."""
    if not _LOADED:
        _LOADED["module"] = harness.load_module(".", "reference_scmoe")
    return _LOADED["module"]


def _tokens(seed, length):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], length)


@pytest.mark.parametrize("held", [None, [0, 8], [2, 4], [6, 2]])
def test_full_forward_equals_the_reference(reference, held):
    """Every layer's equations, and the same share of the experts.  The
    tolerance is float32's: both sides compute in float32 on the CPU and
    differ by the order of their sums alone (logits of order 4)."""
    sizes = dict(TINY, held_experts=held)
    model = ShortcutMoELM(**sizes)
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(1, 40)
    reference.configure(**sizes)
    want = reference._reference_logits(params, jnp.asarray(tokens))
    got = model(params, tokens[None])[0]
    assert got.shape == (40, TINY["vocab_size"])
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_the_reference_and_the_model_lay_their_weights_out_alike(reference):
    sizes = dict(TINY, held_experts=[2, 4])
    ours = jax.tree.map(lambda a: (a.shape, a.dtype), ShortcutMoELM(
        **sizes).init(jax.random.PRNGKey(0), jnp.bfloat16))
    theirs = jax.tree.map(lambda a: (a.shape, a.dtype),
                          reference.make_weights(7, **sizes))
    assert ours == theirs
    layer = ours["layers"][0]
    assert sorted(layer) == ["blocks", "experts_down", "experts_gate",
                             "experts_up", "router", "router_bias"]
    assert len(layer["blocks"]) == 2
    assert layer["router"] == ((64, 12), jnp.float32)  # all 12 outputs
    assert layer["experts_gate"] == ((4, 64, 32), jnp.bfloat16)  # the held


def _layer_of(held):
    """A double layer's parameters for ``held`` out of one uncut draw."""
    p = ShortcutMoELM(**TINY).init(jax.random.PRNGKey(3))["layers"][1]
    first, count = held
    part = dict(p)
    for name in ("experts_gate", "experts_up", "experts_down"):
        part[name] = p[name][first:first + count]
    return ShortcutMoELM(**dict(TINY, held_experts=held)), part, p


def _unit_rows(key, tokens):
    h = jax.random.normal(key, (tokens, TINY["hidden_size"]))
    return h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)


def _zero_term(model, p, u):
    ids, weights = model.route(p, u)
    return jnp.sum(jnp.where(ids >= TINY["n_routed_experts"], weights, 0.0),
                   -1, keepdims=True) * u


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """The share ties to the model: the held terms of every share of a
    partition of the routed experts, plus the zero-compute term once (every
    chip computes it for its own tokens), equal the reference's uncut
    expert branch."""
    u = _unit_rows(jax.random.PRNGKey(4), 24)
    whole, _, p = _layer_of((0, 8))
    zero = _zero_term(whole, p, u)
    assert float(jnp.abs(zero).max()) > 0  # some token picked an identity
    total = zero
    for first in (0, 2, 4, 6):
        model, part, _ = _layer_of((first, 2))
        total = total + model.expert_branch(part, u)[0] - zero
    want = reference._expert_branch(p, u, reference._frozen(TINY), "float32")
    np.testing.assert_allclose(total, want, atol=2e-5)
    # and the uncut program's branch says the same
    np.testing.assert_allclose(whole.expert_branch(p, u)[0], want, atol=2e-5)


class _RoutedFromTheSecondSubBlock(ShortcutMoELM):
    """Not the architecture: the branch reads the second sub-block's normed
    stream (a plain expert layer beside ``FFN_1``)."""

    def layer(self, p, x, attend, live=None):
        eps = self.rms_norm_eps
        first, second = p["blocks"]
        flat = lambda t: t.reshape(-1, t.shape[-1])
        x = x + attend(0, first, rms_norm(x, first["attn_norm"], eps))
        u = flat(rms_norm(x, first["ffn_norm"], eps))
        x = x + gated(u, first["gate"], first["up"],
                      first["down"]).reshape(x.shape)
        x = x + attend(1, second, rms_norm(x, second["attn_norm"], eps))
        h = flat(rms_norm(x, second["ffn_norm"], eps))
        branch, counts = self.expert_branch(p, h)
        x = x + gated(h, second["gate"], second["up"],
                      second["down"]).reshape(x.shape)
        return x + branch.reshape(x.shape), counts


class _AddedBeforeTheSecondFeedForward(ShortcutMoELM):
    """Not the architecture: routed from ``u`` as it should be, but added
    back before ``FFN_1``, which then sees it."""

    def layer(self, p, x, attend, live=None):
        eps = self.rms_norm_eps
        first, second = p["blocks"]
        flat = lambda t: t.reshape(-1, t.shape[-1])
        x = x + attend(0, first, rms_norm(x, first["attn_norm"], eps))
        u = flat(rms_norm(x, first["ffn_norm"], eps))
        branch, counts = self.expert_branch(p, u)
        x = x + gated(u, first["gate"], first["up"],
                      first["down"]).reshape(x.shape)
        x = x + attend(1, second, rms_norm(x, second["attn_norm"], eps))
        x = x + branch.reshape(x.shape)
        h = flat(rms_norm(x, second["ffn_norm"], eps))
        x = x + gated(h, second["gate"], second["up"],
                      second["down"]).reshape(x.shape)
        return x, counts


@pytest.mark.parametrize("variant", [_RoutedFromTheSecondSubBlock,
                                     _AddedBeforeTheSecondFeedForward])
def test_the_shortcut_is_pinned(reference, variant):
    """A layer that routes from the second sub-block's stream, or adds the
    branch before ``FFN_1``, is told apart from the reference: its logits
    lie a thousand times the comparison's tolerance away."""
    model = variant(**TINY)
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(1, 40)
    reference.configure(**TINY)
    want = reference._reference_logits(params, jnp.asarray(tokens))
    got = model(params, tokens[None])[0]
    assert float(jnp.abs(got - want).max()) > 3e-2
    sound = ShortcutMoELM(**TINY)(params, tokens[None])[0]
    np.testing.assert_allclose(sound, want, atol=3e-5)


def test_a_token_whose_picks_are_all_zero_compute_gets_its_input_weighed():
    """Exactly ``(sum w) x u``: the walk adds nothing (no tile does work),
    and the identity term is the whole of the branch."""
    model, p, _ = _layer_of((0, 8))
    u = _unit_rows(jax.random.PRNGKey(5), 16)
    bias = jnp.zeros(12).at[jnp.asarray([8, 10, 11])].set(10.0)
    p = dict(p, router_bias=bias)
    ids, weights = model.route(p, u)
    assert sorted(np.unique(np.asarray(ids))) == [8, 10, 11]
    got, (counts, tiles, touched, zero, most) = model.expert_branch(p, u)
    np.testing.assert_array_equal(got, weights.sum(-1, keepdims=True) * u)
    assert (int(counts.sum()), int(tiles), int(touched)) == (0, 0, 0)
    assert (int(zero), int(most)) == (16 * 3, 0)


def test_the_bias_picks_and_does_not_weigh_and_nothing_is_normalised():
    """A large bias on one expert puts it first into every token's top k,
    and its weight is still 6 x its softmax score; the picked weights sum to
    6 x the picked scores' sum, well under 6."""
    model, p, _ = _layer_of((0, 8))
    u = jax.random.normal(jax.random.PRNGKey(9), (16, TINY["hidden_size"]))
    biased = dict(p, router_bias=jnp.zeros(12).at[5].set(10.0))
    ids, weights = model.route(biased, u)
    assert (ids[:, 0] == 5).all()  # picked first, by score + bias
    scores = jax.nn.softmax(u @ p["router"], axis=-1)
    np.testing.assert_allclose(
        weights, 6.0 * jnp.take_along_axis(scores, ids, axis=-1), rtol=1e-5)
    # normalised, every row would sum to 6; had the bias weighed, over 60
    assert float(weights.sum(-1).max()) < 5.9
    assert float(weights.sum(-1).min()) > 0.0


@pytest.mark.parametrize("flag", ["mla_scale_q_lora", "mla_scale_kv_lora"])
def test_the_lora_scales_are_sqrt_of_hidden_over_rank(flag):
    """``s_q = sqrt(64 / 24)`` on the queries, ``s_kv = sqrt(64 / 16) = 2``
    on the normed latent (not on the rotary key); off, neither."""
    on = ShortcutMoELM(**TINY)
    off = ShortcutMoELM(**dict(TINY, **{flag: False}))
    b = on.init(jax.random.PRNGKey(2))["layers"][0]["blocks"][1]
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 9, TINY["hidden_size"]))
    positions = jnp.arange(9)[None]
    scaled, plain = on.latent(b, h, positions), off.latent(b, h, positions)
    ratio = {"mla_scale_q_lora": ((64 / 24) ** 0.5,) * 2 + (1.0, 1.0),
             "mla_scale_kv_lora": (1.0, 1.0, 2.0, 1.0)}[flag]
    for got, want, r in zip(scaled, plain, ratio):  # q_n, q_r, c, k_r
        np.testing.assert_allclose(got, r * want, rtol=1e-5, atol=1e-6)
    # the normed latent has unit mean square before its scale
    c = scaled[2] if flag == "mla_scale_q_lora" else plain[2] * 2.0
    np.testing.assert_allclose(jnp.mean(c * c, -1), 4.0, rtol=1e-3)


@pytest.mark.parametrize("rows,want", [
    (128 * 12, 16),         # the served step: a mean group of 2
    (96 * 12, 16), (256 * 12, 16), (512 * 12, 16), (768 * 12, 16),
    (1024 * 12, 32), (2048 * 12, 64)])
def test_the_tiles_height_at_this_routers_width(rows, want):
    """``tile_height`` is called with the router's 768 outputs and ``tokens
    x 12`` rows, which keeps the mean group at ``tokens / 64``."""
    assert tile_height(rows, 768) == want


@pytest.mark.parametrize("held", [(0, 8), (4, 4), (6, 2)])
def test_the_branch_equals_the_reference_for_live_and_dead_tokens(
        reference, held):
    """The walk called with 12 outputs of which 4 have no weights: the
    held terms and the zero-compute term against the reference's dense loop
    under its mask, and the counts against the routing."""
    model, part, p = _layer_of(held)
    tokens = 40
    u = _unit_rows(jax.random.PRNGKey(10), tokens)
    live = jnp.arange(tokens) % 4 != 0
    got, (counts, tiles, touched, zero, most) = model.expert_branch(
        part, u, live)
    want = reference._expert_branch(
        part, u, reference._frozen(dict(TINY, held_experts=list(held))),
        "float32")
    np.testing.assert_allclose(got, want, atol=2e-5)
    ids = np.asarray(model.route(p, u)[0])[np.asarray(live)]
    sizes = np.bincount(ids.reshape(-1), minlength=12)
    np.testing.assert_array_equal(counts, sizes[held[0]:sum(held)])
    assert int(zero) == int(sizes[8:].sum())
    assert int(most) == int((ids < 8).sum(-1).max())
    assert int(tiles) >= int(touched) >= int((counts > 0).sum())


def test_the_blocks_counters_take_the_branchs_counts():
    """``observe`` on hand-made ``aux``: two double layers of a step over 6
    live tokens of 3 picks each."""
    from distkeras_tpu.telemetry.metrics import Registry

    model = ShortcutMoELM(**dict(TINY, held_experts=[2, 4]))
    spec = model.decode_spec(None)
    assert spec.state == (("latent_0", 24), ("latent_1", 24))
    assert spec.num_layers == 2 and spec.window is None and spec.shard is None
    registry = Registry()
    instruments = spec.instruments(registry)
    counts = lambda *v: np.asarray(v, np.int32)
    i32 = np.int32
    aux = ((counts(5, 0, 1, 0), i32(3), i32(2), i32(6), i32(3)),
           (counts(0, 2, 2, 0), i32(2), i32(2), i32(9), i32(2)))
    spec.observe(instruments, aux, 6, True)
    snapshot = registry.snapshot()
    value = lambda name: snapshot[name]["value"]
    assert value("serving_moe_tiles_total") == 5
    assert value("serving_moe_experts_touched_total") == 4
    assert value("serving_moe_assignments_total") == 6 * 3 * 2
    assert value("serving_moe_assignments_held_total") == 10
    assert value("serving_moe_assignments_zero_total") == 15
    # a token's real picks average 3 - 6/6 = 2 and 3 - 9/6 = 1.5; the most
    # were 3 and 2: (3/2 + 2/1.5) / 2
    real = snapshot["serving_moe_real_picks_max_over_mean"]
    assert real["count"] == 1
    assert real["sum"] == pytest.approx((1.5 + 2 / 1.5) / 2)
    # a prefill observes no ratio
    spec.observe(instruments, aux, 6, False)
    assert registry.snapshot()[
        "serving_moe_real_picks_max_over_mean"]["count"] == 1
