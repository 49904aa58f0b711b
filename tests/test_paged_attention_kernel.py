"""The Pallas kernel of the width-1 step's paged read
(``ops.pallas.paged_attention``), run by the TPU interpreter (its DMAs and
semaphores simulated) after the step's rows are written, as
``serving.cache.paged_decode_attention`` runs it on a TPU: held against
that function's own gathered read (the path the CPU takes) and against a
dense float32 reference written here, for multi-head float32 pools in
GPT-2's layout and grouped-query bfloat16 pools whose keys and values
differ in width, and for 20 query heads over one key-value head (a group
that the kernel pads to whole tiles of rows); and which shapes the compiled
kernel is chosen for."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas import paged_attention, paged_attention_fits
from distkeras_tpu.serving.cache import (
    append_rows,
    decode_block_pages,
    paged_decode_attention,
)

PAGE, PAGES_PER_SLOT = 16, 20
CTX = PAGE * PAGES_PER_SLOT                               # 320 positions
BLOCK = PAGE * decode_block_pages(PAGE, PAGES_PER_SLOT)   # 128: three blocks,
# the last with 4 of its 8 pages: the rest read the scratch page, masked

#: one slot a case: its ``pos`` (the inactive slot's table is all scratch)
CASES = {
    "pos_0": 0,
    "a_blocks_last_row": BLOCK - 1,
    "the_next_blocks_first_row": BLOCK,
    "mid_block": BLOCK + 77,
    "at_capacity": CTX - 1,
    "inactive_on_the_scratch_page": 0,
}
SLOTS = len(CASES)

#: (kv_heads, query heads a kv head, head_dim, v_dim, pool dtype, tolerance)
GEOMETRIES = {
    # GPT-2's layout at a small size: 2 heads of 64, a 128-lane row
    "multi_head_float32": (2, 1, 64, 64, jnp.float32, 1e-5),
    # the global layers' layout at a small size: keys 2 x 192, values 2 x 128
    "grouped_query_bfloat16": (2, 4, 192, 128, jnp.bfloat16, 5e-3),
    # the Jamba attention layers' layout: 20 query heads over one key-value
    # head of 128, a group the kernel pads to 24 rows
    "twenty_over_one_bfloat16": (1, 20, 128, 128, jnp.bfloat16, 5e-3),
}


def dense_reference(kpool, vpool, tables, pos, q, k, v):
    """The step the plain way, in float32 numpy: write the rows, then every
    slot's whole window at once under ``key_pos <= pos``; key-value head
    ``i`` serves query heads ``i * group .. (i + 1) * group - 1``."""
    kpool = np.array(kpool, np.float32)
    vpool = np.array(vpool, np.float32)
    slots, _, heads, head_dim = q.shape
    kv_heads, v_dim = v.shape[2], v.shape[3]
    for s in range(slots):
        page, offset = tables[s, pos[s] // PAGE], pos[s] % PAGE
        kpool[page, offset] = k[s, 0].reshape(-1)
        vpool[page, offset] = v[s, 0].reshape(-1)
    out = np.zeros((slots, 1, heads, v_dim), np.float32)
    for s in range(slots):
        live = pos[s] + 1
        kw = kpool[tables[s]].reshape(CTX, kv_heads, head_dim)[:live]
        vw = vpool[tables[s]].reshape(CTX, kv_heads, v_dim)[:live]
        for h in range(heads):
            kv = h // (heads // kv_heads)
            sc = kw[:, kv] @ q[s, 0, h] / math.sqrt(head_dim)
            w = np.exp(sc - sc.max())
            out[s, 0, h] = (w / w.sum()) @ vw[:, kv]
    return kpool, vpool, out


@jax.jit
def kernel_step(kpool, vpool, tables, pos, q, k, v):
    """``paged_decode_attention``'s TPU path, interpreted: the rows written
    in place, then the kernel's read of the pools as they lie."""
    kpool = append_rows(kpool, tables, pos, k)
    vpool = append_rows(vpool, tables, pos, v)
    out = paged_attention(kpool, vpool, tables, pos, q[:, 0],
                          block_pages=decode_block_pages(PAGE, PAGES_PER_SLOT),
                          interpret=pltpu.InterpretParams())
    return kpool, vpool, out[:, None]


#: the gathered read, as the CPU runs the function
gathered_step = jax.jit(paged_decode_attention)


@pytest.fixture(scope="module", params=list(GEOMETRIES))
def step(request):
    kv_heads, group, head_dim, v_dim, dtype, tol = GEOMETRIES[request.param]
    heads = kv_heads * group
    rng = np.random.default_rng(38)
    num_pages = SLOTS * PAGES_PER_SLOT + 1
    pools = [jnp.asarray(rng.normal(size=(num_pages, PAGE, kv_heads * d)),
                         dtype) for d in (head_dim, v_dim)]
    tables = 1 + rng.permutation(SLOTS * PAGES_PER_SLOT).reshape(
        SLOTS, PAGES_PER_SLOT).astype(np.int32)
    tables[-1] = 0  # the inactive slot: every row points at scratch
    pos = np.asarray(list(CASES.values()), np.int32)
    q = rng.normal(size=(SLOTS, 1, heads, head_dim)).astype(np.float32)
    k = rng.normal(size=(SLOTS, 1, kv_heads, head_dim)).astype(np.float32)
    v = rng.normal(size=(SLOTS, 1, kv_heads, v_dim)).astype(np.float32)
    args = (jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(q),
            jnp.asarray(k, dtype), jnp.asarray(v, dtype))
    got = kernel_step(*pools, *args)
    gathered = gathered_step(*pools, *args)
    want = dense_reference(*pools, tables, pos, q, np.asarray(args[3]),
                           np.asarray(args[4]))
    return dict(pools=[np.asarray(p, np.float32) for p in pools],
                tables=tables, pos=pos, tol=tol,
                got=[np.asarray(a, np.float32) for a in got],
                gathered=[np.asarray(a, np.float32) for a in gathered],
                want=want)


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_the_gathered_read_and_the_dense_reference(step, case):
    s = list(CASES).index(case)
    tol = step["tol"]
    np.testing.assert_allclose(step["got"][2][s], step["gathered"][2][s],
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(step["got"][2][s], step["want"][2][s],
                               rtol=tol, atol=tol)


def test_no_row_but_the_steps_changes(step):
    """The kernel reads the pools in place: after the step they hold the
    rows written at ``pos`` and nothing else new (the inactive slot's row
    lands on the scratch page, which is nobody's), as after the gathered
    read."""
    written = {(int(step["tables"][s, p // PAGE]), int(p % PAGE))
               for s, p in enumerate(step["pos"])}
    assert (0, 0) in written
    for before, got, gathered, want in zip(step["pools"], step["got"][:2],
                                           step["gathered"][:2],
                                           step["want"][:2]):
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, gathered)
        changed = np.argwhere((got != before).any(-1))
        assert {tuple(map(int, rc)) for rc in changed} <= written


# --------------------------------------------------- the shapes it is chosen for


def _shapes(pages, width, v_width, heads, head_dim, dtype, page=16):
    pool = lambda w: jax.ShapeDtypeStruct((pages, page, w), dtype)
    return (pool(width), pool(v_width),
            jax.ShapeDtypeStruct((4, heads, head_dim), jnp.float32),
            decode_block_pages(page, 64))


@pytest.mark.parametrize("shapes,takes", [
    # the GPT-2 cell's pools, and one tensor-parallel shard of them
    (_shapes(1537, 768, 768, 12, 64, jnp.float32), True),
    (_shapes(1537, 384, 384, 6, 64, jnp.float32), True),
    # the global layers' keys and values of two widths, 16 heads a kv head
    (_shapes(26625, 768, 512, 64, 192, jnp.bfloat16), True),
    # rows narrower than the lanes (the CPU suite's engines)
    (_shapes(41, 32, 32, 4, 8, jnp.float32), False),
    # a page that is not whole tiles of rows of its type
    (_shapes(41, 768, 768, 12, 64, jnp.bfloat16, page=8), False),
    (_shapes(41, 768, 768, 12, 64, jnp.float32, page=4), False),
    # 4 query heads a key-value head: the kernel pads the group to 8 rows
    (_shapes(41, 384, 256, 8, 192, jnp.bfloat16), True),
    # a type the kernel does not take
    (_shapes(41, 768, 768, 12, 64, jnp.float16), False),
    # the Jamba cell's attention layers: 20 query heads over one of 128
    (_shapes(40961, 128, 128, 20, 128, jnp.bfloat16), True),
])
def test_the_kernel_is_chosen_from_the_shapes_alone(shapes, takes):
    assert paged_attention_fits(*shapes) is takes
