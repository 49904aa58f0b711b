"""Paged KV cache for the serving engine.

The training/generation caches (``TransformerLM``'s per-block
``cached_key``/``cached_value`` buffers, ``StagedLM.init_cache``) are
*request-shaped*: one contiguous ``[batch, max_len, heads, head_dim]``
buffer per request batch, allocated for the worst case and thrown away when
the generate call returns.  A serving engine admitting and retiring requests
mid-flight needs the vLLM formulation instead: K/V live in fixed **pools of
pages** shared by every slot, and each slot owns a small *page table* mapping
its logical context chunks to physical pages.  Admission allocates pages,
retirement frees them — the pools themselves never change shape, so the
jitted decode step compiles exactly once.

Layout::

    k_pages, v_pages : num_layers arrays [num_pages, page_size, heads*head_dim]
    tables           : [num_slots, pages_per_slot] int32 (host, numpy)

One array a layer, a token's heads side by side in its row: a row fills the
TPU's 128 lanes whatever the head width, a page is one contiguous run, and
the chip's own layout for the array is the row-major one.  (A single
``[layers, pages, page, heads, head_dim]`` array is laid out by the chip with
the pages' axis on the lanes; every program then re-laid out the whole pool,
and every layer a slice of it, before it could read a page: PERF.md, PR 28.)
Every program takes the two tuples donated and writes them in place.

Physical page 0 is a reserved **scratch page**: unallocated table entries
and inactive slots point at it, so masked-off lanes of the decode step write
garbage there instead of corrupting live pages.  Attention masks by position
(``key_pos <= pos``), so scratch garbage is never read.

What a decode step reads: :func:`paged_decode_attention` writes the step's
row through the table and then walks each slot's pages a block at a time, as
far as the longest live slot reaches (``pos``), with a running maximum,
denominator and weighted sum — never a slot's whole window at once, and
nothing of the pages past the live length.

The pools are plain jax arrays owned by the engine (donated through its jit
step and reassigned from its outputs); this class owns the *bookkeeping*:
free-list, per-slot tables, alloc/free.  Host-side only — nothing here is
traced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PagedKVCache", "append_rows", "rollback_rows",
           "paged_decode_attention", "decode_block_pages"]


# ------------------------------------------------------- traced pool writes
#
# The functions below are the *traced* companions to the host-side
# bookkeeping; each takes ONE layer's pool ``[pages, page_size, width]``.
# ``append_rows`` scatters token rows into it through a slot's page table
# (the decode step's one row, or the ``m``-row window a speculative verify
# feeds); ``rollback_rows`` erases the rejected suffix of that window so the
# pools only ever hold accepted-token K/V between engine iterations;
# ``paged_decode_attention`` is the width-1 step's write and read.


def append_rows(pool, tables, pos, rows):
    """Scatter ``rows [slots, m, heads, head_dim]`` into one layer's ``pool``
    at logical positions ``pos + 0 .. pos + m-1`` of each slot, through
    ``tables [slots, pages_per_slot]``.  Positions at or past a slot's
    capacity (``pages_per_slot * page_size``) are redirected to the scratch
    page, so a speculative window overhanging the end of context can never
    clobber another slot's pages — ``max_context`` stays honest."""
    page_size = pool.shape[1]
    pages_per_slot = tables.shape[1]
    m = rows.shape[1]
    logical = pos[:, None] + jnp.arange(m)[None, :]  # [slots, m]
    page_ix = jnp.clip(logical // page_size, 0, pages_per_slot - 1)
    phys = jnp.take_along_axis(tables, page_ix, axis=1)
    phys = jnp.where(logical < pages_per_slot * page_size, phys, 0)
    rows = rows.reshape(rows.shape[0], m, pool.shape[2])
    return pool.at[phys, logical % page_size].set(rows)


def rollback_rows(pool, tables, pos, count, m):
    """Zero the rejected suffix of an ``m``-row verify window: rows
    ``pos + count .. pos + m-1`` of each slot.  Kept rows (and overhang past
    capacity) are redirected to the scratch page, where the zero-write is
    harmless.  Defensive hygiene more than correctness: attention masks
    ``key_pos <= pos`` and every future write window starts at the live
    position, so stale rows would be overwritten before they could ever be
    attended — but zeroing them keeps the pools' invariant ("only accepted
    tokens between iterations") checkable."""
    page_size = pool.shape[1]
    pages_per_slot = tables.shape[1]
    offs = jnp.arange(m)[None, :]
    logical = pos[:, None] + offs  # [slots, m]
    rejected = (offs >= count[:, None]) & (logical < pages_per_slot * page_size)
    page_ix = jnp.clip(logical // page_size, 0, pages_per_slot - 1)
    phys = jnp.take_along_axis(tables, page_ix, axis=1)
    phys = jnp.where(rejected, phys, 0)
    zeros = jnp.zeros((pos.shape[0], m, pool.shape[2]), pool.dtype)
    return pool.at[phys, logical % page_size].set(zeros)


def decode_block_pages(page_size: int, pages_per_slot: int) -> int:
    """Pages that :func:`paged_decode_attention` reads at a time: some 128
    positions (a block of ``[slots, 128, width]`` is a few megabytes: large
    enough to run at the memory's rate, small enough that a slot shorter
    than the longest wastes little), never more than a slot has."""
    return max(1, min(pages_per_slot, 128 // page_size))


def paged_decode_attention(kpool, vpool, tables, pos, q, k, v):
    """The width-1 step of one layer over all slots: write the step's K and V
    row at ``pos`` through ``tables`` (in place: the pools are donated), then
    attend ``q`` over each slot's positions ``0 .. pos``.

    ``kpool``/``vpool`` ``[pages, page_size, heads*head_dim]``; ``tables
    [slots, pages_per_slot]``; ``pos [slots]``; ``q``/``k``/``v`` ``[slots,
    1, heads, head_dim]`` (the head count is read off them, so the
    tensor-parallel build hands in its local heads and its local pools).
    Returns ``(kpool, vpool, out [slots, 1, heads, head_dim])``.

    The read goes block by block (:func:`decode_block_pages` pages of every
    slot at a time, gathered through the table) with an online softmax in
    float32 — running maximum, denominator and weighted sum — and stops
    after the block that holds the longest slot's ``pos``: pages past the
    live length are never touched, and ``[slots, context, heads, head_dim]``
    is never built.  Within a block the per-slot mask ``key_pos <= pos``
    hides what a shorter slot has not written (or the scratch page that an
    inactive slot's table points at).  Block 0 always holds position 0, so
    every slot has a finite maximum before a fully masked block can meet it.
    """
    slots, _, heads, head_dim = q.shape
    page_size = kpool.shape[1]
    pages_per_slot = tables.shape[1]
    kpool = append_rows(kpool, tables, pos, k)
    vpool = append_rows(vpool, tables, pos, v)

    bp = decode_block_pages(page_size, pages_per_slot)
    span = bp * page_size  # positions a block
    nblocks = -(-pages_per_slot // bp)
    # a last, partial block reads the scratch page for the rows it lacks
    padded = jnp.pad(tables, ((0, 0), (0, nblocks * bp - pages_per_slot)))
    q0 = q[:, 0].astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(head_dim).astype(jnp.float32)

    def block(j):
        """Scores ``[slots, heads, span]`` and values of block ``j``."""
        tb = jax.lax.dynamic_slice_in_dim(padded, j * bp, bp, axis=1)
        kb = kpool[tb].reshape(slots, span, heads, head_dim)
        vb = vpool[tb].reshape(slots, span, heads, head_dim)
        sc = jnp.einsum("shd,skhd->shk", q0, kb.astype(jnp.float32)) * scale
        key_pos = j * span + jnp.arange(span)
        live = key_pos[None, :] <= pos[:, None]
        return jnp.where(live[:, None, :], sc, -jnp.inf), vb

    # block 0 outside the loop: the running state then starts from data (a
    # finite maximum, and under shard_map the heads' varying type), not from
    # constants that the loop would have to reconcile with what it carries
    sc, vb = block(0)
    m = jnp.max(sc, axis=-1)
    p = jnp.exp(sc - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("shk,skhd->shd", p, vb.astype(jnp.float32))

    def merge(j, carry):
        m, l, acc = carry
        sc, vb = block(j)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "shk,skhd->shd", p, vb.astype(jnp.float32))
        return m_new, l, acc

    live_blocks = jnp.max(pos) // span + 1
    m, l, acc = jax.lax.fori_loop(1, live_blocks, merge, (m, l, acc))
    out = (acc / l[..., None]).astype(q.dtype)
    return kpool, vpool, out[:, None]


class PagedKVCache:
    """Page-table bookkeeping plus the pooled K/V buffers.

    ``pages_per_slot`` rows of the table bound each slot's context to
    ``pages_per_slot * page_size`` tokens; ``num_pages`` bounds the fleet of
    pages (default: enough for every slot at full context, plus the scratch
    page — i.e. no over-subscription unless the caller asks for it).
    """

    def __init__(self, *, num_layers, num_slots, page_size, pages_per_slot,
                 heads, head_dim, num_pages=None, dtype=jnp.float32):
        if page_size < 1 or pages_per_slot < 1 or num_slots < 1:
            raise ValueError("page_size, pages_per_slot, num_slots must be >= 1")
        self.num_layers = int(num_layers)
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.pages_per_slot = int(pages_per_slot)
        if num_pages is None:
            num_pages = num_slots * pages_per_slot + 1  # +1 scratch
        if num_pages < 2:
            raise ValueError("need at least one real page beyond scratch")
        self.num_pages = int(num_pages)
        shape = (self.num_pages, self.page_size, int(heads) * int(head_dim))
        self.k_pages = tuple(jnp.zeros(shape, dtype)
                             for _ in range(self.num_layers))
        self.v_pages = tuple(jnp.zeros(shape, dtype)
                             for _ in range(self.num_layers))
        # host-side: table rows point at scratch (page 0) until allocated
        self.tables = np.zeros((self.num_slots, self.pages_per_slot), np.int32)
        # LIFO free list over physical pages 1..num_pages-1 (0 = scratch)
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._owned = {s: [] for s in range(self.num_slots)}

    # ------------------------------------------------------------- queries

    def pages_needed(self, length: int) -> int:
        """Pages required to hold ``length`` tokens of context."""
        return -(-int(length) // self.page_size)  # ceil div

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def max_context(self) -> int:
        """Tokens a single slot can hold: its table rows times page size."""
        return self.pages_per_slot * self.page_size

    # ------------------------------------------------------- alloc / free

    def alloc(self, slot: int, n: int) -> None:
        """Give ``slot`` ``n`` physical pages (admission).  Raises when the
        pool is dry or the slot's table would overflow — the engine checks
        :meth:`can_alloc` first, so hitting either is a bookkeeping bug."""
        owned = self._owned[slot]
        if len(owned) + n > self.pages_per_slot:
            raise ValueError(
                f"slot {slot}: {len(owned)}+{n} pages exceeds table size "
                f"{self.pages_per_slot}"
            )
        if n > len(self._free):
            raise ValueError(f"page pool dry: want {n}, have {len(self._free)}")
        for _ in range(n):
            page = self._free.pop()
            self.tables[slot, len(owned)] = page
            owned.append(page)

    def free(self, slot: int) -> int:
        """Return every page ``slot`` owns to the pool (retirement); the
        slot's table rows point back at scratch.  Returns the count freed."""
        owned = self._owned[slot]
        n = len(owned)
        while owned:
            self._free.append(owned.pop())
        self.tables[slot, :] = 0
        return n
