"""The width-1 decode step's read of a paged key-value cache, as one Pallas
TPU kernel.

The serving engine keeps a layer's keys and values in pools of pages, one
``[pages, page_size, width]`` array a kind, a position's heads side by side
in a row (``serving/cache.py``), and each slot owns a table of the pages it
holds.  Read with XLA's own operations, a step gathers a block of pages for
every slot as far as the longest slot reaches, re-lays the gathered rows
out head by head and takes two products: three passes over HBM where the
live rows need one (PERF.md).  This kernel takes the pools as they
lie, in HBM, and for each slot copies its own pages through its own table
into VMEM, block after block, stopping after the block that holds that
slot's position; the next block's copy runs while this one's products do.

What a block body hands the units:

* **Rows as they lie.**  A block is ``[span, kv_heads * head_dim]``, every
  head on its lanes; nothing is re-laid out per head.  The scores are one
  product of the block against the slot's **block-diagonal query**
  ``[heads, kv_heads * head_dim]`` (row ``h`` holds query head ``h`` in the
  lanes of its key-value head and zeros elsewhere), the weighted sum one
  product of the weights ``[heads, span]`` against the value block, of which
  each head keeps its own key-value head's lanes.  Multi-head attention is
  the case of one query head a key-value head; grouped-query attention
  shares a block among ``group`` query heads, whose rows are padded to whole
  tiles of 8 (20 heads over one key-value head take 24 rows; the padding
  rows are zeros, and dropped from the output).  The zeros cost columns and
  rows of the products, not bytes.
* **The precision of the read it replaces.**  The products' operands go
  to the MXU in the pools' type with float32 accumulation: bfloat16 pools
  at the default precision (the query and the weights rounded to bfloat16,
  as XLA's product rounds them), float32 pools at the highest (XLA takes
  the multi-head products on the VPU in float32; one bfloat16 pass read
  4,000 times further from a float64 reference on a v5e, the highest as
  near as XLA and a tenth slower: PERF.md).  The zeros are exact.
  The running maximum, the denominator and the weighted sum stay float32.
* **Per-slot bounds.**  Block 0 always holds position 0, so every slot has
  a finite maximum before a masked key meets it; within a slot's last block
  the positions past its ``pos`` are masked.  A slot whose table points at
  the scratch page reads one block of it, masked past position 0.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "paged_attention_fits"]

_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b.T: contract the minor axis of both
_NN = (((1,), (0,)), ((), ()))  # a @ b
#: sublanes of a tile by the pools' dtype: a page is whole tiles of rows
_SUBLANES = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}


def paged_attention_fits(kpool, vpool, q, block_pages) -> bool:
    """Does the compiled kernel take these arrays?  Only what the shapes and
    types say: pools of float32 or bfloat16 whose rows are the heads side by
    side at a whole number of the 128 lanes, a page of whole tiles of rows,
    a block of whole lanes of positions, and query heads that the key-value
    heads divide (any number a key-value head: the kernel pads a group to
    whole tiles of rows).  Arguments as :func:`paged_attention` takes
    them."""
    dtype = jnp.dtype(kpool.dtype)
    heads, head_dim = q.shape[-2:]
    width, v_width = kpool.shape[-1], vpool.shape[-1]
    if (dtype not in _SUBLANES or vpool.dtype != dtype or width % head_dim
            or width % _LANES or v_width % _LANES):
        return False
    kv_heads = width // head_dim
    page = kpool.shape[1]
    span = page * block_pages
    group = heads // kv_heads
    return (heads == kv_heads * group and v_width % kv_heads == 0
            and page % _SUBLANES[dtype] == 0 and span % _LANES == 0)


class _Geometry(NamedTuple):
    """All the kernel knows, static."""
    heads: int           # query heads
    rows: int            # query heads padded to whole tiles of rows
    kv_heads: int
    group: int           # query heads a key-value head
    group_rows: int      # rows a group takes: 1, or whole tiles of 8
    v_dim: int
    page: int            # positions a page
    block_pages: int
    span: int            # positions a block
    pages_per_slot: int
    scale: float


def _product(a, b, dims):
    """``a`` by ``b`` in float32: bfloat16 operands at the default
    precision, float32 ones at the highest (the module text has why)."""
    exact = a.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if exact else None)


def _kernel(pos_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, m_ref, l_ref, acc_ref, base_ref, *,
            g: _Geometry):
    """One slot a grid step, the slots in order, and one stream of blocks
    through them all, two in VMEM at a time: the live blocks of slot 0,
    then of slot 1, and so on.  Each block starts the copy of the next one
    in the stream (a later slot's first after a slot's last) into the other
    buffer; ``base`` (SMEM) is the buffer that holds the slot's block 0.
    (Three or four buffers read no faster on a v5e: PERF.md.)"""
    s = pl.program_id(0)
    slots = pl.num_programs(0)
    slot_pos = pos_ref[s]
    blocks = slot_pos // g.span + 1

    def copies(slot, j, buf):
        """Block ``j`` of ``slot`` into buffer ``buf``: a copy a page and
        kind, each through the slot's table (rows of a last, partial block
        past the table read the scratch page, masked as past ``pos``)."""
        out = []
        for i in range(g.block_pages):
            at = j * g.block_pages + i
            page = jnp.where(at < g.pages_per_slot, tables_ref[
                slot * g.pages_per_slot + jnp.minimum(at, g.pages_per_slot - 1)
            ], 0)
            rows = pl.ds(i * g.page, g.page)
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], kbuf.at[buf, rows], sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], vbuf.at[buf, rows], sems.at[1, buf]))
        return out

    def start(slot, j, buf):
        @pl.when(slot < slots)
        def _start():
            for copy in copies(slot, j, buf):
                copy.start()

    @pl.when(s == 0)
    def _first():
        base_ref[0] = 0
        start(0, 0, 0)

    base = base_ref[0]
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]  # [rows, kv_heads * head_dim], block-diagonal

    def block(j, carry):
        buf = (base + j) % 2
        # into the buffer of the block before this one: its products are done
        last = j + 1 == blocks
        start(jnp.where(last, s + 1, s), jnp.where(last, 0, j + 1), 1 - buf)
        for copy in copies(s, j, buf):
            copy.wait()  # dklint: disable=DK112 — a DMA semaphore in the kernel
        sc = _product(q, kbuf[buf], _NT) * g.scale
        key_pos = j * g.span + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        sc = jnp.where(key_pos <= slot_pos, sc, -jnp.inf)  # [rows, span]
        # m and l lie on every lane of their row: [rows, 128]
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(sc - pltpu.repeat(m_next, g.span // _LANES, 1))
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * pltpu.repeat(
            alpha, acc_ref.shape[1] // _LANES, 1) + _product(
                p.astype(vbuf.dtype), vbuf[buf], _NN)
        return carry

    jax.lax.fori_loop(0, blocks, block, 0)
    base_ref[0] = (base + blocks) % 2

    out = acc_ref[...] / pltpu.repeat(l_ref[...], acc_ref.shape[1] // _LANES, 1)
    # row r keeps the lanes of its own key-value head; the padding rows none
    row = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    owner = row // g.group_rows
    out = jnp.where((lane // g.v_dim == owner) & (owner < g.kv_heads)
                    & (row % g.group_rows < g.group), out, 0.0)
    if g.group == 1:  # one row: head h's lanes are its only non-zeros
        out = jnp.sum(out, axis=0, keepdims=True)
    else:  # row r of the group gathers its query head of every key-value head
        out = sum(out[i * g.group_rows:(i + 1) * g.group_rows]
                  for i in range(g.kv_heads))
    o_ref[0] = out.astype(o_ref.dtype)


# one trace and one lowering for every layer of a step that calls it alike
@functools.partial(jax.jit,
                   static_argnames=("block_pages", "interpret"))
def paged_attention(kpool, vpool, tables, pos, q, *, block_pages,
                    interpret=False):
    """Attend each slot's query over its positions ``0 .. pos`` of the paged
    keys and values, reading only that slot's pages.

    ``kpool [pages, page_size, kv_heads * head_dim]`` and ``vpool [pages,
    page_size, kv_heads * v_dim]`` (one layer's pools, as they lie: nothing
    is copied or re-laid out); ``tables [slots, pages_per_slot]`` int32, a
    slot's pages in order (page 0 the scratch page); ``pos [slots]`` int32,
    each slot's last live position; ``q [slots, heads, head_dim]``.
    ``kv_heads`` is read off the widths, and key-value head ``i`` serves
    query heads ``i * group .. (i + 1) * group - 1``.  The scores are scaled
    by ``1 / sqrt(head_dim)``.  Returns ``[slots, heads, v_dim]`` in ``q``'s
    dtype.

    A slot's positions are read ``block_pages`` pages at a time with an
    online softmax in float32, and a slot reads the blocks up to the one
    that holds its own ``pos``; the products take their operands in the
    pools' type (float32 at the highest precision) with float32
    accumulation.  The shapes it takes are :func:`paged_attention_fits`'s.
    ``interpret``: a ``pltpu.InterpretParams`` runs the TPU interpreter
    (DMAs and semaphores simulated), as the CPU tests do."""
    slots, heads, head_dim = q.shape
    pages_per_slot = tables.shape[1]
    page = kpool.shape[1]
    kv_heads = kpool.shape[2] // head_dim
    v_width = vpool.shape[2]
    group = heads // kv_heads
    group_rows = 1 if group == 1 else -(-group // 8) * 8
    rows = -(-(kv_heads * group_rows) // 16) * 16
    g = _Geometry(heads=heads, rows=rows, kv_heads=kv_heads, group=group,
                  group_rows=group_rows, v_dim=v_width // kv_heads,
                  page=page, block_pages=block_pages,
                  span=block_pages * page, pages_per_slot=pages_per_slot,
                  scale=1.0 / math.sqrt(head_dim))
    # the block-diagonal query: query head h in row (h // group) x
    # group_rows + h % group, in the lanes of key-value head h // group; a
    # group's padding rows are zeros
    owner = jnp.arange(heads)[:, None] // group == jnp.arange(kv_heads)
    qbd = (q[:, :, None, :] * owner[None, :, :, None].astype(q.dtype))
    qbd = qbd.reshape(slots, kv_heads, group, kv_heads * head_dim)
    qbd = jnp.pad(qbd, ((0, 0), (0, 0), (0, group_rows - group), (0, 0)))
    qbd = jnp.pad(qbd.reshape(slots, kv_heads * group_rows, -1),
                  ((0, 0), (0, rows - kv_heads * group_rows), (0, 0))
                  ).astype(kpool.dtype)

    slot_block = lambda shape: pl.BlockSpec(
        shape, lambda s, *_: (s, 0, 0), memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[slot_block((1, rows, kpool.shape[2])),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot_block((1, g.group_rows, v_width)),
            scratch_shapes=[
                pltpu.VMEM((2, g.span, kpool.shape[2]), kpool.dtype),
                pltpu.VMEM((2, g.span, v_width), vpool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),            # kind, buffer
                pltpu.VMEM((rows, _LANES), jnp.float32),   # running max
                pltpu.VMEM((rows, _LANES), jnp.float32),   # denominator
                pltpu.VMEM((rows, v_width), jnp.float32),  # weighted sum
                pltpu.SMEM((1,), jnp.int32),               # base
            ]),
        # under shard_map the output varies along whatever its inputs vary on
        out_shape=jax.ShapeDtypeStruct(
            (slots, g.group_rows, v_width), q.dtype,
            vma=frozenset().union(*(jax.typeof(x).vma for x in (
                kpool, vpool, tables, pos, q)))),
        # the slots in order: a slot starts copies of the next ones' blocks
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(pos.astype(jnp.int32), tables.astype(jnp.int32).reshape(-1), qbd,
      kpool, vpool)
    # [slots, group_rows, kv_heads * v_dim] -> [slots, heads, v_dim]
    out = out[:, :group].reshape(slots, group, kv_heads, g.v_dim)
    return jnp.swapaxes(out, 1, 2).reshape(slots, heads, g.v_dim)
