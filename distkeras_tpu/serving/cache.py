"""Paged per-layer state for the serving engine.

The training/generation caches (``TransformerLM``'s per-block
``cached_key``/``cached_value`` buffers, ``StagedLM.init_cache``) are
*request-shaped*: one contiguous ``[batch, max_len, heads, head_dim]``
buffer per request batch, allocated for the worst case and thrown away when
the generate call returns.  A serving engine admitting and retiring requests
mid-flight needs the vLLM formulation instead: the state lives in fixed
**pools of pages** shared by every slot, and each slot owns a small *page
table* mapping its logical context chunks to physical pages.  Admission
allocates pages, retirement frees them — the pools themselves never change
shape, so the jitted decode step compiles exactly once.

**Kinds of state.**  What a position keeps in a layer is the served block's
to say (``DecodeSpec.state``, ``(name, row_width)`` pairs), and
:class:`PagedKVCache` builds one tuple of per-layer pools for each kind,
all addressed by the same tables:

* full attention (GPT-2's block): ``k`` and ``v``, a token's heads side by
  side in a row of ``heads * head_dim``;
* latent attention (``LatentMoELM``): one ``latent`` row for all heads, the
  normalised latent and the rotated shared key (``kv_lora_rank +
  qk_rope_head_dim``, 576 for the published shapes: 1,152 bytes a position
  and layer in bfloat16 where the same heads uncompressed keep 40,960).

Layout::

    pools[name] : num_layers arrays [num_pages, page_size, pool_width(row_width)]
    tables      : [num_slots, pages_per_slot] int32 (host, numpy)

One array a layer, a position's state in one row: a row fills the TPU's 128
lanes, a page is one contiguous run, and the chip's own layout for the array
is the row-major one.  (A single ``[layers, pages, page, heads, head_dim]``
array is laid out by the chip with the pages' axis on the lanes; every
program then re-laid out the whole pool, and every layer a slice of it,
before it could read a page: PERF.md, PR 28.  The same happens to a pool
whose rows are no whole number of lanes, so rows wider than the lanes are
padded to them: :func:`pool_width`, PERF.md, PR 31.)  Every program takes
the tuples donated and writes them in place.

Physical page 0 is a reserved **scratch page**: unallocated table entries
and inactive slots point at it, so masked-off lanes of the decode step write
garbage there instead of corrupting live pages.  Attention masks by position
(``key_pos <= pos``), so scratch garbage is never read.

What a decode step reads: :func:`paged_decode_attention` (keys and values)
and :func:`paged_latent_attention` (a latent row) write the step's rows
through the table and then walk each slot's pages a block at a time, as far
as the longest live slot reaches (``pos``), with a running maximum,
denominator and weighted sum — never a slot's whole window at once, and
nothing of the pages past the live length.

**The two latent-attention paths.**  A prefill chunk runs the *expanded*
form in the block itself (per-head keys and values made from the chunk's
latents, attended within the chunk) and writes ``[c, k_r]`` rows; the
width-1 step runs the *absorbed* form here (:func:`paged_latent_attention`):
the query is carried into the latent's space, scored against the cached rows
as they are, and the weighted latent is carried out by the block: one gather
of a block serves all heads and nothing per head is ever cached.

The pools are plain jax arrays owned by the engine (donated through its jit
step and reassigned from its outputs); :class:`PagedKVCache` owns the
*bookkeeping*: free-list, per-slot tables, alloc/free.  Host-side only —
nothing there is traced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PagedKVCache", "append_rows", "rollback_rows",
           "paged_decode_attention", "paged_latent_attention",
           "decode_block_pages", "pool_width", "fit_rows"]


# ------------------------------------------------------- traced pool writes
#
# The functions below are the *traced* companions to the host-side
# bookkeeping; each takes ONE layer's pool ``[pages, page_size, width]``.
# ``append_rows`` scatters token rows into it through a slot's page table
# (the decode step's one row, or the ``m``-row window a speculative verify
# feeds); ``rollback_rows`` erases the rejected suffix of that window so the
# pools only ever hold accepted-token K/V between engine iterations;
# ``paged_decode_attention`` is the width-1 step's write and read.


#: the TPU's lanes: a pool's rows are whole multiples of them (below)
LANES = 128


def pool_width(row_width: int) -> int:
    """The width a pool is allocated at for rows of ``row_width``: rows wider
    than the chip's 128 lanes are padded to whole lanes.  A row-major pool
    takes that room on the chip whatever its logical width, and for a width
    that is no multiple of the lanes (latent attention's 576) the chip's own
    layout for the array is not the row-major one: every program would then
    re-lay the whole pool out on its way in and out (PERF.md, PR 31)."""
    if row_width <= LANES:
        return row_width
    return -(-row_width // LANES) * LANES


def fit_rows(rows, pool):
    """``rows [..., width]`` in ``pool``'s type, zero-padded to its width."""
    short = pool.shape[-1] - rows.shape[-1]
    if short:
        rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, short)])
    return rows.astype(pool.dtype)


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
    rows = fit_rows(rows.reshape(rows.shape[0], m, -1), pool)
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


def _attend_live_blocks(block, weigh, pos, span):
    """The online softmax over the live blocks, shared by the two single-token
    attentions.  ``block(j)`` gives block ``j``'s masked float32 scores
    ``[slots, heads, span]`` and its values; ``weigh(p, values)`` their
    weighted sum under the block's weights ``p``.  Returns the denominator
    ``[slots, heads]`` and the weighted sum, both float32, after the block
    that holds the longest slot's ``pos``."""
    # block 0 outside the loop: the running state then starts from data (a
    # finite maximum, and under shard_map the heads' varying type), not from
    # constants that the loop would have to reconcile with what it carries
    sc, vb = block(0)
    m = jnp.max(sc, axis=-1)
    p = jnp.exp(sc - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = weigh(p, vb)

    def merge(j, carry):
        m, l, acc = carry
        sc, vb = block(j)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + weigh(p, vb)
        return m_new, l, acc

    live_blocks = jnp.max(pos) // span + 1
    _, l, acc = jax.lax.fori_loop(1, live_blocks, merge, (m, l, acc))
    return l, acc


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

    weigh = lambda p, vb: jnp.einsum("shk,skhd->shd", p, vb.astype(jnp.float32))
    l, acc = _attend_live_blocks(block, weigh, pos, span)
    out = (acc / l[..., None]).astype(q.dtype)
    return kpool, vpool, out[:, None]


def paged_latent_attention(pool, tables, pos, q, row, latent_width, scale):
    """The width-1 step of one *latent-attention* layer over all slots, in
    the absorbed form: a position keeps ONE row for all heads (``[c, k_r]``:
    the normalised latent and the rotated shared key), and a head's query is
    handed in already carried into the latent's space (``[q_n W_uk, q_r]``),
    so that the score of a head against a position is one dot product with
    the cached row and the weighted sum is taken over the rows' first
    ``latent_width`` columns (the latent itself; the caller carries it out
    through ``W_uv``).  Nothing per head is ever cached or expanded: one
    gather of a block serves every head.

    ``pool [pages, page_size, width]``; ``q [slots, heads, width]``; ``row
    [slots, width]``, written at ``pos`` first (in place: the pool is
    donated).  Returns ``(pool, out [slots, heads, latent_width])`` in
    float32.  The products take the pool's type for both operands and
    accumulate in float32; the maximum, the denominator and the weighted sum
    run in float32, block by block as :func:`paged_decode_attention` does,
    stopping after the block that holds the longest slot's ``pos``.

    The other path, for a prefill chunk, is the block's own: it expands the
    chunk's latents to per-head keys and values, attends within the chunk,
    and writes the same ``[c, k_r]`` rows (``models/latent_moe.py``)."""
    slots = q.shape[0]
    page_size = pool.shape[1]
    pages_per_slot = tables.shape[1]
    pool = append_rows(pool, tables, pos, row[:, None])

    bp = decode_block_pages(page_size, pages_per_slot)
    span = bp * page_size
    nblocks = -(-pages_per_slot // bp)
    padded = jnp.pad(tables, ((0, 0), (0, nblocks * bp - pages_per_slot)))
    q = fit_rows(q, pool)  # a pool's lane padding is nought: it scores nought

    def block(j):
        tb = jax.lax.dynamic_slice_in_dim(padded, j * bp, bp, axis=1)
        rows = pool[tb].reshape(slots, span, pool.shape[2])
        sc = jnp.einsum("shw,skw->shk", q, rows,
                        preferred_element_type=jnp.float32) * scale
        key_pos = j * span + jnp.arange(span)
        live = key_pos[None, :] <= pos[:, None]
        return jnp.where(live[:, None, :], sc, -jnp.inf), rows[..., :latent_width]

    weigh = lambda p, cb: jnp.einsum("shk,skc->shc", p.astype(cb.dtype), cb,
                                     preferred_element_type=jnp.float32)
    l, acc = _attend_live_blocks(block, weigh, pos, span)
    return pool, acc / l[..., None]


class PagedKVCache:
    """Page-table bookkeeping plus the pooled per-layer state.

    ``state`` is what the served block keeps a position and layer, as
    ``(name, row_width)`` pairs (:class:`~distkeras_tpu.models.decode.
    DecodeSpec` ``.state``): ``(("k", w), ("v", w))`` for full attention's
    keys and values, ``(("latent", 576),)`` for latent attention's one row
    for all heads.  Each kind is a tuple of ``num_layers`` pools ``[pages,
    page_size, row_width]`` under ``pools[name]`` (also ``cache.<name>_pages``);
    all kinds share the tables, the free list and the scratch page.
    ``heads``/``head_dim`` without ``state`` mean the keys and values of
    that geometry.

    ``pages_per_slot`` rows of the table bound each slot's context to
    ``pages_per_slot * page_size`` tokens; ``num_pages`` bounds the fleet of
    pages (default: enough for every slot at full context, plus the scratch
    page — i.e. no over-subscription unless the caller asks for it).
    """

    def __init__(self, *, num_layers, num_slots, page_size, pages_per_slot,
                 state=None, heads=None, head_dim=None, num_pages=None,
                 dtype=jnp.float32):
        if page_size < 1 or pages_per_slot < 1 or num_slots < 1:
            raise ValueError("page_size, pages_per_slot, num_slots must be >= 1")
        if state is None:
            state = (("k", int(heads) * int(head_dim)),
                     ("v", int(heads) * int(head_dim)))
        self.state = tuple((str(name), int(width)) for name, width in state)
        self.num_layers = int(num_layers)
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.pages_per_slot = int(pages_per_slot)
        if num_pages is None:
            num_pages = num_slots * pages_per_slot + 1  # +1 scratch
        if num_pages < 2:
            raise ValueError("need at least one real page beyond scratch")
        self.num_pages = int(num_pages)
        self.pools = {
            name: tuple(jnp.zeros((self.num_pages, self.page_size,
                                   pool_width(width)), dtype)
                        for _ in range(self.num_layers))
            for name, width in self.state}
        # host-side: table rows point at scratch (page 0) until allocated
        self.tables = np.zeros((self.num_slots, self.pages_per_slot), np.int32)
        # LIFO free list over physical pages 1..num_pages-1 (0 = scratch)
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._owned = {s: [] for s in range(self.num_slots)}

    def __getattr__(self, attr):
        # cache.k_pages, cache.latent_pages: one kind's pools by its name
        pools = self.__dict__.get("pools", {})
        if attr.endswith("_pages") and attr[:-len("_pages")] in pools:
            return pools[attr[:-len("_pages")]]
        raise AttributeError(attr)

    # ------------------------------------------------------------- queries

    def bytes_per_position(self) -> int:
        """Bytes of state the block keeps a position, over all layers and
        kinds: its declared rows in the pools' type (a pool's lane padding,
        :func:`pool_width`, is not the block's)."""
        return sum(width * self.pools[name][0].dtype.itemsize * self.num_layers
                   for name, width in self.state)

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
