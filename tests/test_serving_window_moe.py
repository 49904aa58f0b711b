"""``WindowMoELM`` through ``ServingEngine``: a block whose layers keep state
of two kinds and two sizes, global layers' keys and values paged a position
and sliding layers' in a ring of ``window`` rows a slot, served by the same
loop and the same programs' builders.  At a small size on the CPU in
float32, prefill and then decoding through both agree with the plain
reference's full-context pass at every generated position (logits by the
block's own ``DecodeSpec`` functions over the engine's stores; the engine's
served tokens by the reference's gaps), for prompts shorter than the window,
as long and longer, across page boundaries and across positions ``window -
1``, ``window`` and ``window + 1``; the ring holds ``window`` rows a slot and
is taken over cleanly by the next request; the block's counters match hand
counts; the engine refuses the builds the block does not bring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import WindowMoELM
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.serving import GenerateRequest, ServingEngine
from distkeras_tpu.serving.cache import (PagedKVCache, fit_rows,
                                         sliding_decode_attention,
                                         sliding_prefill_rows)
from distkeras_tpu.telemetry.metrics import Registry

from test_window_moe import TINY
from test_window_moe import reference as _reference  # noqa: F401 (fixture)

HELD = [4, 4]  # four of the sixteen experts: a chip's share
SIZES = dict(TINY, held_experts=HELD)
WINDOW, LAYERS = TINY["sliding_window"], TINY["num_hidden_layers"]
SLIDING = TINY["hybrid_layer_pattern"].count(1)


@pytest.fixture
def reference(_reference):
    _reference.configure(**SIZES)
    return _reference


@pytest.fixture(scope="module")
def lm():
    model = WindowMoELM(**SIZES)
    return model, model.init(jax.random.PRNGKey(11))


def _prompt(seed, length):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], length).tolist()


def _counter(registry, name):
    return registry.snapshot()[name]["value"]


@pytest.mark.parametrize("plen", [5, 8, 13])
def test_prefill_then_decode_equals_the_reference_logits(lm, reference,
                                                         plen):
    """The block's own ``prefill`` and ``step`` over the engine's stores
    (pages of 4 in a table in reverse order for the global layers, slot 1 of
    a two-slot ring for the sliding ones), teacher-forced: the logits of the
    prompt's last row and of 10 decoded positions against the reference's
    full forward.  Prompts shorter than the window of 8, as long and
    longer; the steps cross positions 7, 8 and 9 (or run from 13 on) and
    page boundaries.  Tolerance float32's (logits of order 4)."""
    model, params = lm
    spec = model.decode_spec(params)
    page, pages, steps, slot = 4, 8, 10, 1
    cache = PagedKVCache(num_layers=LAYERS, num_slots=2, page_size=page,
                         pages_per_slot=pages, state=spec.state,
                         layer_kinds=spec.layer_kinds, sliding=spec.sliding)
    windows = dict(spec.sliding)
    table = jnp.arange(pages, 0, -1, dtype=jnp.int32)
    sequence = _prompt(7, plen + steps)
    want = reference._reference_logits(params, jnp.asarray(sequence))
    pools = {name: list(layers) for name, layers in cache.pools.items()}
    width = 16  # the prompt's chunk, padded to whole pages
    chunk = jnp.zeros((1, width), jnp.int32).at[0, :plen].set(
        jnp.asarray(sequence[:plen]))
    positions = jnp.arange(width)[None]
    x = spec.embed(params, chunk, positions)
    for li in range(LAYERS):
        def write(name, rows, li=li):
            pool = pools[name][li]
            if name in windows:
                pools[name][li] = sliding_prefill_rows(pool, slot, rows, plen)
            else:
                pools[name][li] = pool.at[table[:width // page]].set(
                    fit_rows(rows.reshape(width // page, page, -1), pool))
        x, _ = spec.prefill(params, li, x, positions, write, positions < plen)
    np.testing.assert_allclose(spec.head(params, x, at=plen - 1),
                               want[plen - 1], atol=3e-5)
    tables = jnp.stack([jnp.zeros_like(table), table])
    live = jnp.asarray([[False], [True]])
    for at in range(plen, plen + steps):
        pos = jnp.asarray([0, at], jnp.int32)
        x = spec.embed(params, jnp.asarray([[0], [sequence[at]]]),
                       pos[:, None])
        for li in range(LAYERS):
            kinds = spec.kinds(li)
            layer, x, _ = spec.step(
                params, li, x, {n: pools[n][li] for n in kinds}, tables, pos,
                live)
            for name in kinds:
                pools[name][li] = layer[name]
        np.testing.assert_allclose(spec.head(params, x)[slot, 0], want[at],
                                   atol=3e-5)
    # the ring holds the window and no more: position j at row j % 8
    ring = pools["sliding_k"][1]
    assert ring.shape == (3, WINDOW, 96)
    assert float(jnp.abs(ring[0]).max()) == 0.0  # slot 0 took no part


def test_the_ring_is_set_up_whole_at_admission_and_written_in_place():
    """``sliding_prefill_rows`` writes all ``window`` rows of the slot: the
    prompt's last rows at ``j % window`` and zeros before position 0, over
    whatever the slot held; ``sliding_decode_attention`` writes a live slot's
    row at ``pos % window`` and a slot that takes no part writes to the
    scratch slot."""
    store = jnp.ones((3, 4, 2))
    rows = jnp.arange(24, dtype=jnp.float32).reshape(12, 2) + 100
    short = sliding_prefill_rows(store, 1, rows, 3)
    np.testing.assert_array_equal(short[1], [[100, 101], [102, 103],
                                             [104, 105], [0, 0]])
    assert float(short[0].min()) == float(short[2].min()) == 1.0
    long = sliding_prefill_rows(store, 0, rows, 10)  # positions 6 .. 9
    np.testing.assert_array_equal(long[0, :, 0], [116, 118, 112, 114])
    q = jnp.ones((2, 1, 2, 2))
    k = jnp.full((2, 1, 1, 2), 7.0)
    keys, values, out = sliding_decode_attention(
        long, long, jnp.asarray([10, 5]), jnp.asarray([True, False]), q, k,
        k, None, 1.0)
    np.testing.assert_array_equal(keys[0, 10 % 4], [7, 7])
    np.testing.assert_array_equal(keys[1], store[1])  # untouched
    np.testing.assert_array_equal(keys[2, 5 % 4], [7, 7])  # the scratch slot
    assert out.shape == (2, 1, 2, 2)


def test_the_engine_serves_it_and_counts_what_the_windows_save(lm, reference):
    """Five requests on two slots, so that slots and their rings are given
    back and taken mid-flight (a long request's ring is taken by a prompt
    shorter than the window); prompts of 3 to 29 tokens on pages of 4.  The
    served token is the full-context reference's best everywhere.  The
    sliding counters by hand: per decode step and sliding layer, the window
    read and ``pos + 1`` live; the prefills' pairs: a chunk of ``W`` within
    one block of queries scores ``W x W`` in every layer against ``W(W +
    1) / 2`` causal."""
    model, params = lm
    registry = Registry()
    engine = ServingEngine(model, params, num_slots=2, page_size=4,
                           registry=registry)
    mix = [(29, 12), (6, 9), (3, 14), (13, 6), (8, 10)]
    try:
        rings = engine._cache.pools["sliding_v"]
        assert [r is None for r in rings] == [True, False, False, True, False]
        assert rings[1].shape == (3, WINDOW, 64)
        pending = [engine.submit(GenerateRequest(
            prompt=_prompt(plen, plen), max_new_tokens=new, temperature=0.0))
            for plen, new in mix]
        results = [p.result(timeout=600) for p in pending]
        assert engine._decode._cache_size() == 1  # one program, never retraced
        widths = [next(w for w in engine.prefill_buckets if w >= plen)
                  for plen, _ in mix]
        stats = engine.stats()
    finally:
        engine.stop()
    for (plen, new), result in zip(mix, results):
        assert result.finish_reason == "length" and len(result.tokens) == new
        gaps = reference.served_gaps(params, _prompt(plen, plen),
                                     result.tokens, TINY["max_len"])
        assert float(gaps.max()) <= 1e-5, (plen, new, gaps)
    steps = sum(new - 1 for _, new in mix)
    live = sum(pos + 1 for plen, new in mix
               for pos in range(plen, plen + new - 1))
    assert _counter(registry, "serving_sliding_positions_read_total") == (
        SLIDING * WINDOW * steps)
    assert _counter(registry, "serving_sliding_positions_live_total") == (
        SLIDING * live)
    assert _counter(registry, "serving_prefill_attn_pairs_total") == (
        LAYERS * sum(w * w for w in widths))
    assert _counter(registry, "serving_prefill_attn_causal_pairs_total") == (
        LAYERS * sum(w * (w + 1) // 2 for w in widths))
    # paged a position: two global layers of 2 x 24 keys and 2 x 16 values;
    # fixed a slot: three sliding layers of 8 rows of 4 x 24 and 4 x 16
    per_position = 2 * (48 + 32) * 4
    per_slot = 3 * WINDOW * (96 + 64) * 4
    assert _counter(registry, "serving_state_per_position_bytes") == (
        per_position)
    assert _counter(registry, "serving_state_per_slot_bytes") == per_slot
    assert (stats["state_per_position_bytes"],
            stats["state_per_slot_bytes"]) == (per_position, per_slot)
    assert _counter(registry, "serving_moe_assignments_total") == (
        sum(plen + new - 1 for plen, new in mix) * 4 * 4)
    assert _counter(registry, "serving_kv_pages_in_use") == 0


def test_a_hot_swap_keeps_the_layout(lm):
    model, params = lm
    engine = ServingEngine(model, params, num_slots=2, page_size=4,
                           registry=Registry(), dtype="bfloat16")
    try:
        before = engine.generate(_prompt(1, 11), max_new_tokens=5,
                                 timeout=600)
        engine.hot_swap(model, model.init(jax.random.PRNGKey(12)), timeout=60)
        after = engine.generate(_prompt(1, 11), max_new_tokens=5,
                                timeout=600)
        assert before.tokens != after.tokens
        wider = WindowMoELM(**dict(SIZES, sliding_window=16))
        with pytest.raises(ValueError, match="geometry"):
            engine.hot_swap(wider, wider.init(jax.random.PRNGKey(0)))
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
        ServingEngine(model, params, num_slots=2, page_size=4,
                      registry=Registry(), **kwargs)
