"""The width-1 step's attention over the paged pools
(``serving.cache.paged_decode_attention``): held against a dense float32
reference written here, on one device and under the engine's one-axis
tensor-parallel mesh (the CPU's virtual devices); the in-place write; what
the lowered decode step must not hold; and the two host-side counters that
say how far the bound by live length engages."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distkeras_tpu.models import TransformerLM
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.serving.cache import (
    decode_block_pages,
    paged_decode_attention,
)
from distkeras_tpu.telemetry.metrics import Registry
from distkeras_tpu.utils import compat

PAGE, PAGES_PER_SLOT, HEADS, HEAD_DIM = 4, 40, 4, 8
CTX = PAGE * PAGES_PER_SLOT            # 160 positions a slot
BLOCK = PAGE * decode_block_pages(PAGE, PAGES_PER_SLOT)  # 128: two blocks,
# the second with 8 of its 32 pages: the rest read the scratch page, masked

#: one slot a case: its ``pos`` (the inactive slot's table is all scratch)
CASES = {
    "pos_0": 0,
    "one_under_a_page_boundary": PAGE - 1,
    "one_over_a_page_boundary": PAGE,
    "one_under_a_block_boundary": BLOCK - 1,
    "one_over_a_block_boundary": BLOCK,
    "max_context_less_1": CTX - 1,
    "inactive_on_the_scratch_page": 0,
}
SLOTS = len(CASES)
LAYOUTS = ("one_device", "mesh")


def dense_reference(kpool, vpool, tables, pos, q, k, v):
    """The same step the plain way, in float32 numpy: write the row, then
    every slot's whole window at once under the mask ``key_pos <= pos``."""
    kpool, vpool = np.array(kpool), np.array(vpool)
    for s in range(len(pos)):
        page, offset = tables[s, pos[s] // PAGE], pos[s] % PAGE
        kpool[page, offset] = k[s, 0].reshape(-1)
        vpool[page, offset] = v[s, 0].reshape(-1)
    out = np.zeros((len(pos), 1, HEADS, HEAD_DIM), np.float32)
    for s in range(len(pos)):
        live = pos[s] + 1
        kw = kpool[tables[s]].reshape(CTX, HEADS, HEAD_DIM)[:live]
        vw = vpool[tables[s]].reshape(CTX, HEADS, HEAD_DIM)[:live]
        sc = np.einsum("hd,khd->hk", q[s, 0], kw) / math.sqrt(HEAD_DIM)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[s, 0] = np.einsum("hk,khd->hd", w, vw)
    return kpool, vpool, out


@pytest.fixture(scope="module", params=LAYOUTS)
def step(request):
    """One call of the function over all the cases' slots, and the
    reference's answer to the same inputs."""
    rng = np.random.default_rng(28)
    num_pages = SLOTS * PAGES_PER_SLOT + 1
    width = HEADS * HEAD_DIM
    pools = [rng.normal(size=(num_pages, PAGE, width)).astype(np.float32)
             for _ in range(2)]
    tables = 1 + rng.permutation(SLOTS * PAGES_PER_SLOT).reshape(
        SLOTS, PAGES_PER_SLOT).astype(np.int32)
    tables[-1] = 0  # the inactive slot: every row points at scratch
    pos = np.asarray(list(CASES.values()), np.int32)
    q, k, v = (rng.normal(size=(SLOTS, 1, HEADS, HEAD_DIM)).astype(np.float32)
               for _ in range(3))

    fn = paged_decode_attention
    if request.param == "mesh":
        # as the engine's tensor-parallel build calls it: pools and heads
        # sharded over the one axis, tables and positions replicated, and
        # JAX proving the varying-axes types (check_vma)
        pool, heads = P(None, None, "model"), P(None, None, "model", None)
        fn = compat.shard_map(
            fn, make_mesh(2, axis_name="model"),
            in_specs=(pool, pool, P(), P(), heads, heads, heads),
            out_specs=(pool, pool, heads), check_vma=True)
    compiled = jax.jit(fn)
    got = compiled(*map(jnp.asarray, (*pools, tables, pos, q, k, v)))
    want = dense_reference(*pools, tables, pos, q, k, v)
    return dict(pools=pools, tables=tables, pos=pos, k=k, v=v,
                got=[np.asarray(a) for a in got], want=want)


@pytest.mark.parametrize("case", list(CASES))
def test_matches_dense_reference_and_reads_its_row_back(step, case):
    s = list(CASES).index(case)
    np.testing.assert_allclose(step["got"][2][s], step["want"][2][s],
                               rtol=1e-5, atol=1e-5)
    page = step["tables"][s, step["pos"][s] // PAGE]
    offset = step["pos"][s] % PAGE
    for pool, row in zip(step["got"][:2], (step["k"], step["v"])):
        np.testing.assert_array_equal(pool[page, offset], row[s, 0].reshape(-1))


def test_no_other_row_changes(step):
    """In place means: the rows written at ``pos`` and nothing else (the
    inactive slot's row lands on the scratch page, which is nobody's)."""
    for before, got, want in zip(step["pools"], step["got"][:2],
                                 step["want"][:2]):
        np.testing.assert_array_equal(got, want)
        changed = np.argwhere((got != before).any(-1))
        written = {(int(step["tables"][s, p // PAGE]), int(p % PAGE))
                   for s, p in enumerate(step["pos"])}
        assert {tuple(map(int, rc)) for rc in changed} == written
        assert (0, 0) in written  # the scratch page took the inactive row


# ------------------------------------------------------- the lowered step


def _tiny(heads):
    module = TransformerLM(vocab_size=23, dim=8 * heads, heads=heads,
                           num_layers=2, max_len=160)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, 4), np.int32))["params"]
    return module, params


def _lowered_decode(engine):
    return engine._decode.lower(
        engine._spec.params(), engine._cache.k_pages, engine._cache.v_pages,
        jnp.asarray(engine._cache.tables), jnp.asarray(engine._pos),
        jnp.asarray(engine._last), jnp.asarray(engine._keys),
        jnp.asarray(engine._temp), jnp.asarray(engine._topk),
        jnp.asarray(engine._topp), jnp.asarray(engine._active),
    ).as_text()


@pytest.mark.parametrize("layout", LAYOUTS)
def test_lowered_decode_step_never_holds_a_whole_window(layout):
    """Neither ``[slots, ctx, heads, head_dim]`` nor the gathered pages
    ``[slots, pages_per_slot, page, width]`` may appear in the step, whole
    or as a mesh shard; each layer's pool goes in and out as it is."""
    heads, shards = 4, (2 if layout == "mesh" else 1)
    module, params = _tiny(heads)
    mesh = make_mesh(shards, axis_name="model") if shards > 1 else None
    engine = ServingEngine(module, params, num_slots=3, page_size=8,
                           registry=Registry(), mesh=mesh)
    try:
        text = _lowered_decode(engine)
    finally:
        engine.stop()
    slots, ctx, pages, page = 3, 160, 20, 8
    for h in {heads, heads // shards}:
        assert f"tensor<{slots}x{ctx}x{h}x8xf32>" not in text
        assert f"tensor<{slots}x{pages}x{page}x{8 * h}xf32>" not in text
        assert f"tensor<{slots}x{ctx}x{8 * h}xf32>" not in text
    num_pages = engine._cache.num_pages
    assert f"tensor<{num_pages}x{page}x{8 * heads}xf32>" in text
    assert f"tensor<2x{num_pages}x" not in text  # no pool of all the layers
    assert "stablehlo.while" in text  # the loop over the live blocks


# ------------------------------------------------------------ the counters


def test_kv_read_counters_follow_the_lengths():
    """After the steps of a known mix, read over capacity is what the
    lengths say: each step counts, for its one active slot, ``pos + 1``
    rounded up to the block of 128 (capped at the slot's 160), against
    3 slots x 160."""
    module, params = _tiny(2)
    registry = Registry()
    engine = ServingEngine(module, params, num_slots=3, page_size=8,
                           registry=registry)
    assert engine._kv_block == 128
    mix = [(5, 4), (120, 12), (140, 3)]  # (prompt tokens, new tokens)
    try:
        for plen, new in mix:
            got = engine.generate([1 + i % 22 for i in range(plen)], max_new_tokens=new,
                                  timeout=300)
            assert len(got.tokens) == new
    finally:
        engine.stop()
    read = steps = 0
    for plen, new in mix:
        # the prefill makes the first token; step i feeds position plen + i
        for fed in range(plen, plen + new - 1):
            read += min(160, 128 * -(-(fed + 1) // 128))
            steps += 1
    snap = registry.snapshot()
    assert snap["serving_decode_steps_total"]["value"] == steps
    assert snap["serving_decode_kv_positions_read_total"]["value"] == read
    assert snap["serving_decode_kv_positions_capacity_total"][
        "value"] == steps * 3 * 160
    assert read == 3 * 128 + (8 * 128 + 3 * 160) + 2 * 160  # by hand
