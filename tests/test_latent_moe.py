"""``LatentMoELM`` (latent attention over expert layers) at a small size on
the CPU, in float32 on seeded weights, against the benchmark's plain
reference (``benchmark/reference_mla_moe.py``, one file, loaded by path as a
run loads it): the full forward, the chip's share of an expert layer tied to
the uncut layer, the absorbed step against the expanded form on the same
rows, picking against weighing, and a routing in which one expert takes
every token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import LatentMoELM
from distkeras_tpu.models.latent_moe import rms_norm, tile_height
from distkeras_tpu.serving.cache import paged_latent_attention

#: hidden 64, 4 heads, latent 16, rope 8, 8 experts top-2 with 1 shared, 1
#: dense + 2 expert layers
TINY = dict(
    vocab_size=97, max_len=64, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, intermediate_size=128,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    num_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1, "original_max_position_embeddings": 16,
                  "type": "deepseek_yarn"})


_LOADED = {}


@pytest.fixture
def reference(harness):
    """One instance for the file: its jitted layers compile once."""
    if not _LOADED:
        _LOADED["module"] = harness.load_module(".", "reference_mla_moe")
    return _LOADED["module"]


def _tokens(seed, length):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], length)


@pytest.mark.parametrize("held", [None, [0, 8], [2, 4], [6, 2]])
def test_full_forward_equals_the_reference(reference, held):
    """(a) every layer's equations, and the same share of the experts."""
    sizes = dict(TINY, held_experts=held)
    model = LatentMoELM(**sizes)
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(1, 40)
    reference.configure(**sizes)
    want = reference._reference_logits(params, jnp.asarray(tokens))
    got = model(params, tokens[None])[0]
    assert got.shape == (40, TINY["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_reference_and_the_model_lay_their_weights_out_alike(reference):
    model = LatentMoELM(**dict(TINY, held_experts=[2, 4]))
    ours = jax.tree.map(lambda a: a.shape, model.init(jax.random.PRNGKey(0)))
    theirs = jax.tree.map(lambda a: a.shape, reference.make_weights(
        7, **dict(TINY, held_experts=[2, 4])))
    assert ours == theirs


def _expert_layer(held):
    """An expert layer's parameters for ``held`` out of one uncut draw."""
    whole = LatentMoELM(**TINY)
    p = whole.init(jax.random.PRNGKey(3))["layers"][1]
    first, count = held
    part = dict(p)
    for name in ("experts_gate", "experts_up", "experts_down"):
        part[name] = p[name][first:first + count]
    return LatentMoELM(**dict(TINY, held_experts=held)), part, p


def _unit_rows(key, tokens):
    """Rows of unit mean square: the reference's layer normalises its input
    itself, and under a unit weight leaves such rows as they are."""
    h = jax.random.normal(key, (tokens, TINY["hidden_size"]))
    return h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)


def _reference_layer(reference, p, h, held=None):
    cast, operand = reference._arithmetic("float32")
    return reference._feed_forward(
        dict(p, ffn_norm=jnp.ones(TINY["hidden_size"])), h,
        dict(TINY, held_experts=held), cast, operand)


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """(c) the share ties to the model: the held parts of all four shares,
    the shared expert counted once, add up to the reference's uncut layer."""
    h = _unit_rows(jax.random.PRNGKey(4), 24)
    whole, _, p = _expert_layer((0, 8))
    shared = whole._gated(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    total = shared
    for first in (0, 2, 4, 6):
        model, part, _ = _expert_layer((first, 2))
        total = total + model.feed_forward(part, h)[0] - shared
    want = _reference_layer(reference, p, h)
    np.testing.assert_allclose(total, want, atol=2e-5)
    # and the uncut program's layer says the same
    np.testing.assert_allclose(whole.feed_forward(p, h)[0], want, atol=2e-5)


def test_the_absorbed_step_equals_the_expanded_form_on_the_same_rows():
    """(d) the last position of a sequence: attended in the expanded form
    within the chunk, and in the absorbed form over the same rows in pages."""
    model = LatentMoELM(**TINY)
    p = model.init(jax.random.PRNGKey(5))["layers"][0]
    rows, page = 21, 8
    x = jax.random.normal(jax.random.PRNGKey(6), (1, rows, TINY["hidden_size"]))
    positions = jnp.arange(rows)[None]
    q_n, q_r, c, k_r = model.latent(p, rms_norm(x, p["attn_norm"], 1e-6),
                                    positions)
    expanded = model.expanded_attention(p, q_n, q_r, c, k_r)[0, -1]
    # rows 0 .. rows-2 already in the pages (a table in reverse order), the
    # step writes the last
    pages = -(-rows // page)
    table = jnp.arange(pages, 0, -1, dtype=jnp.int32)[None]
    cached = jnp.concatenate([c, k_r], axis=-1)[0]
    padded = jnp.zeros((pages * page, model.row_width)).at[:rows - 1].set(
        cached[:-1])
    pool = jnp.zeros((pages + 1, page, model.row_width)).at[table[0]].set(
        padded.reshape(pages, page, -1))
    pos = jnp.asarray([rows - 1], jnp.int32)
    last = slice(rows - 1, rows)
    pool, absorbed = model.absorbed_step(
        p, pool, table, pos, q_n[:, last], q_r[:, last], c[:, last],
        k_r[:, last])
    np.testing.assert_allclose(absorbed[0, 0], expanded, atol=2e-5)
    # the step's row went through the table to its place
    np.testing.assert_allclose(
        pool[table[0, (rows - 1) // page], (rows - 1) % page], cached[-1],
        atol=1e-6)


def test_paged_latent_attention_stops_at_the_longest_live_slot():
    """Rows past every slot's position are never read: poison there changes
    nothing (two blocks of 128 positions, the longest slot in the first)."""
    slots, heads, width, latent, page, pages = 2, 3, 12, 8, 16, 16
    key = jax.random.split(jax.random.PRNGKey(8), 3)
    pool = jax.random.normal(key[0], (slots * pages + 1, page, width))
    tables = 1 + jnp.arange(slots * pages, dtype=jnp.int32).reshape(slots, pages)
    q = jax.random.normal(key[1], (slots, heads, width))
    row = jax.random.normal(key[2], (slots, width))
    pos = jnp.asarray([5, 100], jnp.int32)
    _, clean = paged_latent_attention(pool, tables, pos, q, row, latent, 0.3)
    poisoned = pool.at[tables[:, 8:]].set(jnp.nan)  # positions 128 and up
    _, got = paged_latent_attention(poisoned, tables, pos, q, row, latent, 0.3)
    np.testing.assert_array_equal(got, clean)
    # by hand, slot 0: softmax over its 6 rows (the step's row is the last)
    rows0 = jnp.concatenate([pool[tables[0, 0]][:5], row[:1]])
    weights = jax.nn.softmax(jnp.einsum("hw,kw->hk", q[0], rows0) * 0.3, -1)
    np.testing.assert_allclose(clean[0], weights @ rows0[:, :latent],
                               atol=1e-5)


def test_the_bias_picks_and_does_not_weigh():
    """(e) a large bias on one expert puts it into every token's top k, and
    its weight is still the score's share: it fails when the bias weighs."""
    model, p, _ = _expert_layer((0, 8))
    h = jax.random.normal(jax.random.PRNGKey(9), (16, TINY["hidden_size"]))
    biased = dict(p, router_bias=jnp.zeros(8).at[5].set(10.0))
    ids, weights = model.route(biased, h)
    assert (ids[:, 0] == 5).all()  # picked first, by score + bias
    scores = jax.nn.sigmoid(h @ p["router"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * 2.5
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    # had the bias weighed, expert 5 would hold nearly all of a token's 2.5
    assert float(weights[:, 0].max()) < 2.0


@pytest.mark.parametrize("held", [(0, 8), (4, 4)])
def test_one_expert_takes_every_token_and_none_is_dropped(reference, held):
    """(e) a routing in which expert 5 is every token's first choice: every
    token gets that expert's term (a capacity would drop most of them), and
    the counts say so."""
    model, part, p = _expert_layer(held)
    tokens = 48
    h = _unit_rows(jax.random.PRNGKey(10), tokens)
    bias = jnp.zeros(8).at[5].set(10.0)
    part, p = dict(part, router_bias=bias), dict(p, router_bias=bias)
    got, counts = model.feed_forward(part, h, jnp.ones(tokens, bool))
    want = _reference_layer(reference, part, h, list(held))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(counts[5 - held[0]]) == tokens  # all of them, none dropped
    ids, _ = model.route(p, h)
    in_share = (ids >= held[0]) & (ids < held[0] + held[1])
    assert int(counts.sum()) == int(in_share.sum())
    # only the live tokens are counted
    half = jnp.arange(tokens) < tokens // 2
    _, counted = model.feed_forward(part, h, half)
    assert int(counted[5 - held[0]]) == tokens // 2


def test_yarn_keeps_fast_dimensions_and_interpolates_slow_ones():
    from distkeras_tpu.models.latent_moe import yarn_inv_freq, yarn_mscale

    scaling = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
               "mscale_all_dim": 1, "original_max_position_embeddings": 4096}
    plain = yarn_inv_freq(64, 10000.0, None)
    scaled = yarn_inv_freq(64, 10000.0, scaling)
    np.testing.assert_allclose(scaled[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(scaled[-8:], plain[-8:] / 40, rtol=1e-6)
    assert (np.diff(scaled / plain) <= 1e-6).all()  # a ramp between
    # by hand: the correction range is dimensions 10 to 23 of the 32
    assert scaled[11] / plain[11] == pytest.approx(1 - (1 - 1 / 40) / 13, 1e-5)
    # m = 0.1 ln 40 + 1; the rotary's own factor m / m is 1
    assert yarn_mscale(scaling, "mscale_all_dim") == pytest.approx(1.36889, 1e-5)
    model = LatentMoELM(vocab_size=8, max_len=8, rope_scaling=scaling)
    assert model.softmax_scale == pytest.approx(192 ** -0.5 * 1.36889 ** 2, 1e-5)


# ------------------------------------------- the walk over row tiles (PR 32)

HEIGHTS = (16, 32, 64, 128, 256)


@pytest.mark.parametrize("rows,experts,want", [
    (64 * 8, 128, 16),      # the served step: a mean group of 4
    (512 * 8, 128, 64), (768 * 8, 128, 64), (1024 * 8, 128, 128),
    (1792 * 8, 128, 128), (2048 * 8, 128, 256), (3072 * 8, 128, 256),
    (7 * 2, 8, 16), (1, 128, 16)])
def test_the_tiles_height_follows_the_traced_rows(rows, experts, want):
    """The power of two at or under twice the mean group, within 16..256:
    the prefill buckets and the step of the served cell, and tiny shapes."""
    assert tile_height(rows, experts) == want
    assert want in HEIGHTS


def _hand_made(case, tile):
    """``(first choices [tokens], tiles, touched)`` for a layer that holds
    experts 2..5 of 8: ``2 * tile + 3`` tokens of two choices each (rows no
    multiple of the tile), the second choice always an absent expert."""
    tokens = 2 * tile + 3
    first = {
        # expert 3 gets nothing, between two that do
        "an_empty_group_in_the_middle":
            [2] * 5 + [4] * (tile - 1) + [5] * 3 + [0] * (tokens - tile - 7),
        "one_expert_takes_every_row": [4] * tokens,
        "one_tile_and_one_tile_plus_a_row":
            [2] * tile + [3] * (tile + 1) + [5] * 2,
        "no_held_row_at_all": [0] * tokens,
    }[case]
    sizes = np.bincount(first, minlength=8)[2:6]
    return (np.asarray(first), int(np.ceil(sizes / tile).sum()),
            int((sizes > 0).sum()))


def _dense_terms(p, h, ids, weights, held):
    """Every held expert over every token, weighed by what routed there."""
    total = 0.0
    for local in range(held[1]):
        term = LatentMoELM._gated(h, p["experts_gate"][local],
                                  p["experts_up"][local],
                                  p["experts_down"][local])
        share = jnp.sum(jnp.where(ids == held[0] + local, weights, 0.0), -1)
        total = total + term * share[:, None]
    return total


@pytest.mark.parametrize("tile", HEIGHTS)
@pytest.mark.parametrize("case", [
    "an_empty_group_in_the_middle", "one_expert_takes_every_row",
    "one_tile_and_one_tile_plus_a_row", "no_held_row_at_all"])
def test_the_walk_equals_a_dense_loop_on_hand_made_groups(case, tile):
    held = (2, 4)
    model, part, _ = _expert_layer(held)
    first, tiles, touched = _hand_made(case, tile)
    tokens = len(first)
    assert tile_height(tokens * 2, 8) == tile  # the rule returns this one
    rng = np.random.default_rng(tile)
    first = rng.permutation(first)
    ids = jnp.asarray(np.stack([first, np.where(first == 7, 6, 7)], axis=1))
    weights = jnp.asarray(rng.uniform(0.2, 2.0, (tokens, 2)), jnp.float32)
    h = _unit_rows(jax.random.PRNGKey(tile), tokens)
    live = jnp.arange(tokens) % 3 != 0
    got, (counts, walked, met) = model.held_experts_terms(
        part, h, ids, weights, live)
    np.testing.assert_allclose(
        got, _dense_terms(part, h, ids, weights, held), atol=2e-5)
    # the counts are the routing's: the live tokens' assignments an expert
    want = np.bincount(first[np.asarray(live)], minlength=8)[2:6]
    np.testing.assert_array_equal(counts, want)
    assert (int(walked), int(met)) == (tiles, touched)


@pytest.mark.parametrize("tile", HEIGHTS)
def test_the_walk_equals_a_dense_loop_on_a_routed_batch(tile):
    """Both choices may be held, as the router leaves them."""
    held = (2, 4)
    model, part, _ = _expert_layer(held)
    tokens = 2 * tile + 3
    h = _unit_rows(jax.random.PRNGKey(100 + tile), tokens)
    ids, weights = model.route(part, h)
    got, (counts, walked, met) = model.held_experts_terms(part, h, ids, weights)
    np.testing.assert_allclose(
        got, _dense_terms(part, h, ids, weights, held), atol=2e-5)
    sizes = np.bincount(np.asarray(ids).reshape(-1), minlength=8)[2:6]
    np.testing.assert_array_equal(counts, sizes)
    assert int(walked) == int(np.ceil(sizes / tile).sum())
    assert int(met) == int((sizes > 0).sum())


def test_the_blocks_counters_take_the_walks_counts():
    """``observe`` on hand-made ``aux``: two expert layers of a step (the
    dense layer hands None), 3 + 2 tiles over 2 + 2 touched experts."""
    from distkeras_tpu.telemetry.metrics import Registry

    model = LatentMoELM(**dict(TINY, held_experts=[2, 4]))
    spec = model.decode_spec(None)
    registry = Registry()
    instruments = spec.instruments(registry)
    counts = lambda *v: np.asarray(v, np.int32)
    aux = (None, (counts(5, 0, 1, 0), np.int32(3), np.int32(2)),
           (counts(0, 2, 2, 0), np.int32(2), np.int32(2)))
    spec.observe(instruments, aux, 6, True)
    value = lambda name: registry.snapshot()[name]["value"]
    assert value("serving_moe_tiles_total") == 5
    assert value("serving_moe_experts_touched_total") == 4
    assert value("serving_moe_assignments_total") == 6 * 2 * 2
    assert value("serving_moe_assignments_held_total") == 10
