"""Fused flash-attention Pallas TPU kernels.

The reference delegates all tensor math to Keras/TF kernels (SURVEY.md §2:
"zero native components"); this module is the TPU-native analogue for the one
op where fusion matters most at long context: attention.  The jnp ring /
local attention in :mod:`distkeras_tpu.parallel.ring` already avoids the
[seq, seq] materialisation at the *inter-device* level; these kernels do the
same at the *intra-device* level — tiled online-softmax in VMEM, so HBM
traffic is O(seq·d) instead of O(seq²), with the matmuls shaped for the MXU.

Forward and backward (FlashAttention-2 style: recompute probabilities
blockwise, separate dQ and dK/dV passes) are both Pallas kernels, joined by a
``jax.custom_vjp``.  On non-TPU backends the same kernels run under the Pallas
interpreter (tests exercise them on the CPU device mesh); production CPU paths
should keep using the jnp fallback in ``parallel.ring``.

What a block body hands the units (v5e, measured: PERF.md section 6, PR 25):

* **Full lanes.**  A 64-wide head alone fills half of every 128-lane tile, in
  HBM and in VMEM, and the DMA, every load and every MXU push pay for the
  empty half: the same work on 128-wide operands runs three times as fast.  So
  a block holds as many heads side by side as fit 128 lanes
  (:func:`_to_blocks`), and a head's product is taken over all the lanes with
  the other heads' lanes set to zero.
* **The operands' own dtype** into the MXU (bf16 stays bf16; the
  probabilities are cast to it where they enter the second matmuls),
  float32 accumulators and float32 softmax statistics.
* **Reductions down sublanes.**  The forward works on the transposed score
  block, [keys, queries]: the running max and the denominator lie along lanes
  and the softmax's max and sum run vreg against vreg, not across lanes.
* **Few equations.**  The trainers trace, transform and lower twelve unrolled
  layers on every start, some 40 ms an equation of a kernel's body, and the
  benchmark holds ``setup_s`` to a tenth.  So a kernel has one body: a
  block's heads are a loop of the device's (7-9% slower than unrolled, a
  quarter fewer equations), and a mask is added to every live block (2% of a
  block; a second, mask-free body would be a fifth more to trace).  Blocks
  above the causal diagonal are skipped and their operands are not fetched.

Layout convention matches the rest of the framework: [batch, seq, heads, dim].
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG_BIG = -1e30  # used instead of -inf so fully-masked rows stay NaN-free
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b.T: contract the minor axis of both
_TN = (((0,), (0,)), ((), ()))  # a.T @ b: contract the major axis of both


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _folds_scale(scale: float, dtype) -> bool:
    """May ``scale`` go into the [rows, width] operand block instead of the
    scores?  Only where that rounds nothing more than the scores' own float32
    product would: float32 blocks, or a power of two."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


class _Geometry(NamedTuple):
    """What one kernel knows about its grid, all static.  A grid step brings
    a [bq, heads * d] block of Q and a [bk, heads * d] block of K/V into VMEM:
    ``heads`` heads of width ``d`` side by side on the lanes."""
    scale: float
    causal: bool
    bq: int
    bk: int
    lk_valid: int
    lk_pad: int
    heads: int
    d: int


def _live(i, j, g: _Geometry):
    """Does score block (q block ``i``, k block ``j``) attend at all?  ``True``
    where the shapes say so, else a traced scalar: blocks wholly above the
    causal diagonal do not."""
    return j * g.bk <= i * g.bq + (g.bq - 1) if g.causal else True


def _bias(shape, q_axis, i, j, g: _Geometry):
    """What to add to score block (i, j), laid out with Q along ``q_axis``: 0
    where the pair attends, ``_NEG_BIG`` above the causal diagonal and on K's
    padded tail; ``None`` where the shapes say no block has either.  Padded Q
    rows need no mask: their ``dO`` is zero, so they add nothing to dK/dV,
    and their own outputs are cut off."""
    if not g.causal and g.lk_valid == g.lk_pad:
        return None
    q_idx = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    keep = None
    if g.causal:
        keep = q_idx - k_idx >= j * g.bk - i * g.bq
    if g.lk_valid < g.lk_pad:
        tail = k_idx < g.lk_valid - j * g.bk
        keep = tail if keep is None else keep & tail
    return jnp.where(keep, 0.0, _NEG_BIG)


def _scores(a, b, fold, bias, g: _Geometry):
    """``a @ b.T`` in float32, scaled unless the scale went into an operand
    (``fold``), masked where ``bias`` says so."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if not fold:
        s = s * g.scale
    return s if bias is None else s + bias


def _for_heads(dtype, g: _Geometry, body):
    """``body(n, mask, fmask)`` for each head ``n`` of a block, as a loop of
    the device's: the body is traced once however many heads share a block.
    ``mask``/``fmask``: a [1, heads * d] row, 1 on the head's lanes and 0 on
    the others, in the operands' ``dtype`` and in float32 (``None`` where a
    block holds one head).  An operand times it, contracted over all the
    lanes, gives that head's product, and the MXU sees a full-width operand
    instead of a d-wide slice; a product times it keeps the head's columns."""
    if g.heads == 1:
        body(0, None, None)
        return

    def step(n, carry):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, g.heads * g.d), 1)
        mask = (lane >= n * g.d) & (lane < (n + 1) * g.d)
        body(n, mask.astype(dtype), mask.astype(jnp.float32))
        return carry

    jax.lax.fori_loop(0, g.heads, step, None)


def _only(x, mask):
    return x if mask is None else x * mask


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _q_major_specs(g: _Geometry):
    """Q-side, K-side and row-statistic specs of a (b, q block, k block) grid.
    A step above the causal diagonal re-uses the K block it has: no fetch."""
    if g.causal:  # the last K block that Q block i sees
        k_map = lambda b, i, j: (
            b, jnp.minimum(j, (i * g.bq + (g.bq - 1)) // g.bk), 0)
    else:
        k_map = lambda b, i, j: (b, j, 0)
    return (_vmem((1, g.bq, g.heads * g.d), lambda b, i, j: (b, i, 0)),
            _vmem((1, g.bk, g.heads * g.d), k_map),
            _vmem((1, g.heads, g.bq), lambda b, i, j: (b, 0, i)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, ot_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, g: _Geometry):
    """The accumulator and the output are transposed like the scores,
    [heads * d, bq]: the caller turns the output."""
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # k block (innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_live(i, j, g))
    def _block():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        fold = _folds_scale(g.scale, q.dtype)
        if fold:
            q = q * g.scale
        bias = _bias((g.bk, g.bq), 1, i, j, g)

        def _head(n, mask, fmask):
            row = pl.ds(n, 1)
            # every query sees key 0 in block j == 0, so its running max is
            # finite from then on and exp(_NEG_BIG - m) is an exact 0
            st = _scores(_only(k, mask), q, fold, bias, g)
            m_prev = m_ref[row]
            m_new = jnp.maximum(m_prev, st.max(axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)
            l_ref[row] = alpha * l_ref[row] + pt.sum(axis=0, keepdims=True)
            m_ref[row] = m_new
            # [heads * d, bq]: the rows of the other heads come out 0 and
            # their accumulators stay as they are
            update = jax.lax.dot_general(
                _only(v, mask), pt.astype(v.dtype), _TN,
                preferred_element_type=jnp.float32)
            if fmask is not None:
                alpha = 1.0 + fmask.T * (alpha - 1.0)
            acc_ref[:] = acc_ref[:] * alpha + update

        _for_heads(k.dtype, g, _head)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # a query's denominator is at least 1: its largest score is among the
        # keys it has seen, and it has seen key 0
        for n in range(g.heads):
            rows = slice(n * g.d, (n + 1) * g.d)
            ot_ref[0, rows] = (acc_ref[rows] / l_ref[n:n + 1]).astype(
                ot_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_ref[:])


def _fwd_call(qt, kt, vt, *, g: _Geometry, interpret):
    """Returns the output transposed, [groups, heads * d, lq], and ``lse``,
    [groups, heads, lq]."""
    groups, lq, width = qt.shape
    q_spec, k_spec, row_spec = _q_major_specs(g)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, g=g),
        grid=(groups, lq // g.bq, g.lk_pad // g.bk),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=[_vmem((1, width, g.bq), lambda b, i, j: (b, 0, i)),
                   row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((groups, width, lq), qt.dtype),
            jax.ShapeDtypeStruct((groups, g.heads, lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((width, g.bq), jnp.float32),    # output accumulator
            pltpu.VMEM((g.heads, g.bq), jnp.float32),  # running max m
            pltpu.VMEM((g.heads, g.bq), jnp.float32),  # running denominator l
        ],
        interpret=interpret,
    )(qt, kt, vt)


# ---------------------------------------------------------------------------
# backward (FlashAttention-2: blockwise recompute; dQ pass + dK/dV pass)
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, g: _Geometry):
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # k block (innermost)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_live(i, j, g))
    def _block():
        q, do, k, v = q_ref[0], do_ref[0], k_ref[0], v_ref[0]
        fold = _folds_scale(g.scale, q.dtype)
        if fold:
            q = q * g.scale
        bias = _bias((g.bq, g.bk), 0, i, j, g)

        def _head(n, mask, fmask):
            s = _scores(_only(q, mask), k, fold, bias, g)
            p = jnp.exp(s - lse_ref[0, n][:, None])
            dp = jax.lax.dot_general(_only(do, mask), v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, n][:, None])
            acc_ref[:] += _only(jnp.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32),
                fmask)

        _for_heads(q.dtype, g, _head)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * g.scale).astype(dq_ref.dtype)


def _dq_call(qt, kt, vt, dot_, lse, delta, *, g: _Geometry, interpret):
    groups, lq, width = qt.shape
    q_spec, k_spec, row_spec = _q_major_specs(g)
    return pl.pallas_call(
        functools.partial(_dq_kernel, g=g),
        grid=(groups, lq // g.bq, g.lk_pad // g.bk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[pltpu.VMEM((g.bq, width), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, g: _Geometry):
    """Works on the transposed score block, [bk, bq]: ``lse`` and ``delta``
    then broadcast along the lanes they are stored on, and dV = Pᵀ·dO and
    dK = dSᵀ·Q are plain products with no transposed operand."""
    j = pl.program_id(1)  # k block
    i = pl.program_id(2)  # q block (innermost)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_live(i, j, g))
    def _block():
        q, do, k, v = q_ref[0], do_ref[0], k_ref[0], v_ref[0]
        fold = _folds_scale(g.scale, k.dtype)
        if fold:
            k = k * g.scale
        bias = _bias((g.bk, g.bq), 1, i, j, g)

        def _head(n, mask, fmask):
            row = pl.ds(n, 1)
            st = _scores(_only(k, mask), q, fold, bias, g)
            pt = jnp.exp(st - lse_ref[0, row])
            dpt = jax.lax.dot_general(_only(v, mask), do, _NT,
                                      preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta_ref[0, row])
            dv_acc[:] += _only(jnp.dot(
                pt.astype(do.dtype), do, preferred_element_type=jnp.float32),
                fmask)
            dk_acc[:] += _only(jnp.dot(
                dst.astype(q.dtype), q, preferred_element_type=jnp.float32),
                fmask)

        _for_heads(k.dtype, g, _head)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * g.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dkv_call(qt, kt, vt, dot_, lse, delta, *, g: _Geometry, interpret):
    groups, lq, width = qt.shape
    # grid transposed: (k block, q block innermost); a step above the diagonal
    # re-uses the first Q block that attends (the last, for keys past them all)
    if g.causal:
        q_of = lambda j, i: jnp.minimum(jnp.maximum(i, (j * g.bk) // g.bq),
                                        lq // g.bq - 1)
    else:
        q_of = lambda j, i: i
    q_spec = _vmem((1, g.bq, width), lambda b, j, i: (b, q_of(j, i), 0))
    k_spec = _vmem((1, g.bk, width), lambda b, j, i: (b, j, 0))
    row_spec = _vmem((1, g.heads, g.bq), lambda b, j, i: (b, 0, q_of(j, i)))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, g=g),
        grid=(groups, g.lk_pad // g.bk, lq // g.bq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[
            pltpu.VMEM((g.bk, width), jnp.float32),
            pltpu.VMEM((g.bk, width), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)


# ---------------------------------------------------------------------------
# public entry — [batch, seq, heads, dim], custom VJP
# ---------------------------------------------------------------------------


def _heads_per_block(h, d):
    """How many heads share a block's 128 lanes: as many as fit, of those
    that divide the head count."""
    n = max(1, _LANES // d)
    while h % n:
        n //= 2
    return n


def _to_blocks(x, lp, n):
    """[b, l, h, d] -> [b * h/n, lp, n * d]: ``n`` heads side by side on the
    minor axis, zero-padded to ``lp`` rows."""
    b, l, h, d = x.shape
    x = x.reshape(b, l, h // n, n * d)
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h // n, l, n * d)
    return x if lp == l else jnp.pad(x, ((0, 0), (0, lp - l), (0, 0)))


def _from_blocks(x, b, l, n):
    """[b * h/n, lp, n * d] -> [b, l, h, d]."""
    groups, _, width = x.shape
    x = jnp.transpose(x[:, :l].reshape(b, groups // b, l, width), (0, 2, 1, 3))
    return x.reshape(b, l, groups // b * n, width // n)


def _rows_to_blocks(x, lp, n):
    """[b, l, h] float32 row statistics -> [b * h/n, n, lp]."""
    b, l, h = x.shape
    x = jnp.transpose(x, (0, 2, 1)).reshape(b * h // n, n, l)
    return x if lp == l else jnp.pad(x, ((0, 0), (0, 0), (0, lp - l)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    interpret=None):
    """Fused attention over [batch, seq, heads, dim] tensors.

    Semantics match ``parallel.ring.local_attention`` (softmax(QKᵀ/√d)·V,
    optional causal mask) but run as tiled Pallas kernels: online softmax in
    VMEM, no [seq, seq] materialisation in HBM, matmul operands in the inputs'
    dtype with f32 accumulation and f32 softmax statistics.
    ``interpret=None`` auto-selects the Pallas interpreter on non-TPU backends
    (used by the CPU-mesh test suite).

    ``block_q``/``block_k`` override the blocks; left ``None``, ``_prep``
    picks them from the lengths: one block of up to 1024 a side.  Measured on
    one TPU v5e at [16, 1024, 12, 64] bf16, causal (the benchmark's GPT-2
    cell; the kernels alone, host clock over 20 calls; executed TFLOP/s
    counts the products of the blocks that run, the useful half of each
    lane-packed one):

    ======  =====  =========  ================
    kernel  block  ms a call  executed TFLOP/s
    ======  =====  =========  ================
    fwd       256      1.758              18.3
    fwd       512      1.034              37.4
    fwd      1024      0.889              58.0
    dq        256      1.531              31.6
    dq        512      0.994              58.3
    dq       1024      0.914              84.6
    dk/dv     256      1.356              47.5
    dk/dv     512      1.199              64.5
    dk/dv    1024      1.209              85.3
    ======  =====  =========  ================

    Smaller blocks skip more of what lies above the diagonal (62.5% of the
    square runs at 256, 75% at 512, all of it at 1024) and lose more than that
    to the grid step and to the loop over a block's heads.  The kernels these
    replaced (float32 operands, one 64-wide head a block, blocks 256 x 512)
    took 2.22 ms forward and 3.4 ms backward.
    """
    return _fa_fwd(q, k, v, causal, block_q, block_k, interpret)[0]


_BLOCK = 1024  # see the table in flash_attention's docstring


def _side(l, block):
    """The block along one sequence side, and the length padded to it."""
    if block is None:
        lp = _round_up(l, 16 if l <= _LANES else _LANES)
        # the largest multiple of 128 that divides the length; all of a short one
        block = lp if lp <= _BLOCK else max(
            b for b in range(_LANES, _BLOCK + 1, _LANES) if lp % b == 0)
        return block, lp
    block = min(block, _round_up(l, 16))
    return block, _round_up(l, block)


def _prep(q, k, causal, block_q, block_k, interpret):
    """The kernels' geometry, the padded lengths and heads a block, from what
    the call can see: lengths, heads, head width, ``causal``."""
    _, lq, h, d = q.shape
    lk = k.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bq, lq_pad = _side(lq, block_q)
    bk, lk_pad = _side(lk, block_k)
    n = _heads_per_block(h, d)
    g = _Geometry(scale=1.0 / (d ** 0.5), causal=causal, bq=bq, bk=bk,
                  lk_valid=lk, lk_pad=lk_pad, heads=n, d=d)
    return g, lq_pad, n, interpret


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    b, lq, h, d = q.shape
    g, lq_pad, n, interpret = _prep(q, k, causal, block_q, block_k, interpret)
    out_t, lse = _fwd_call(
        _to_blocks(q, lq_pad, n), _to_blocks(k, g.lk_pad, n),
        _to_blocks(v, g.lk_pad, n), g=g, interpret=interpret)
    # [b * h/n, n * d, lq_pad] -> [b, lq, h, d]
    out = jnp.transpose(out_t[:, :, :lq].reshape(b, h, d, lq), (0, 3, 1, 2))
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    b, lq = q.shape[:2]
    lk = k.shape[1]
    g, lq_pad, n, interpret = _prep(q, k, causal, block_q, block_k, interpret)
    qt, dot_ = _to_blocks(q, lq_pad, n), _to_blocks(do, lq_pad, n)
    kt, vt = _to_blocks(k, g.lk_pad, n), _to_blocks(v, g.lk_pad, n)
    # delta_i = rowsum(dO_i · O_i); tiny elementwise op, XLA fuses it.
    delta = _rows_to_blocks(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1),
        lq_pad, n)
    dq = _dq_call(qt, kt, vt, dot_, lse, delta, g=g, interpret=interpret)
    dk, dv = _dkv_call(qt, kt, vt, dot_, lse, delta, g=g, interpret=interpret)
    return (_from_blocks(dq, b, lq, n), _from_blocks(dk, b, lk, n),
            _from_blocks(dv, b, lk, n))


flash_attention.defvjp(_fa_fwd, _fa_bwd)
