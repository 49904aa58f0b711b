"""The selective scan of a Mamba layer, as Pallas TPU kernels: ``ssm_scan``
over the positions of a prefill chunk and ``ssm_step`` over one position of
every slot.

A Mamba layer's recurrence, per channel ``c`` and state ``n``, for inputs
``x_t`` (the convolved, activated stream), ``delta_t`` (the step, after its
softplus), ``B_t`` and ``C_t`` (``[d_state]``), ``A = -exp(A_log)`` and ``D``::

    h_t[n, c] = exp(delta_t[c] A[n, c]) h_{t-1}[n, c] + delta_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] h_t[n, c] + D[c] x_t[c]

The state is ``[d_state, channels]`` (16 x 5,120 for the published Mamba
layers): the 16 states on the sublanes, the channels on the lanes.  A plain
``lax.scan`` keeps it in HBM between positions, 0.65 MB written and read
back a position and layer; these kernels keep it in VMEM and read only
``x``, ``delta``, ``B`` and ``C``.  Everything is float32: the state, the
step, its exponential and the ``C`` contraction.

* **``ssm_scan``** (a prefill chunk, one sequence from ``h = 0``): the grid
  runs over blocks of channels and, in order, over blocks of positions; the
  state of a block of channels lives in VMEM scratch from the first block of
  positions to the last, where it is written out whole.  A row whose
  ``delta`` is 0 leaves the state as it was (``exp(0) = 1``, a zero input):
  the caller zeroes the rows past a prompt so that the padding of a chunk
  never reaches the state.
* **``ssm_step``** (one position of every slot): the grid runs over groups
  of slots and blocks of channels; each slot's state is read, advanced one
  position and written back **in place** (the store is aliased to the
  output), only where the slot is live: another slot's state is written back
  as it was.  The store may hold rows past the slots (the engine's scratch
  slot): they are not touched.

``B_t`` and ``C_t`` come as rows; the kernels turn each into a column (the
states on the sublanes) by a product with the identity and a sum along the
lanes, not by a transpose.  :func:`selective_scan` and :func:`selective_step`
choose the kernel when they are lowered for a TPU, at shapes it takes
(:func:`scan_fits`, :func:`step_fits`), and the plain ``jax.numpy``
recurrence (:func:`plain_scan`, :func:`plain_step`) everywhere else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_scan", "ssm_step", "scan_fits", "step_fits", "plain_scan",
           "plain_step", "selective_scan", "selective_step", "SCAN_BLOCKS",
           "STEP_BLOCKS"]

F32 = jnp.float32
_LANES = 128
_SUBLANES = 8
#: ``(channels, positions)`` a grid step of ``ssm_scan`` at most: a state of
#: 16 x 512 float32 is 8 vector registers, carried across the positions.  On
#: a v5e a 512 x 5,120 chunk took 427-470 us at every choice of (256-1,024,
#: 128-512), this one the fastest (PERF.md)
SCAN_BLOCKS = (512, 512)
#: ``(slots, channels)`` a grid step of ``ssm_step`` at most: on a v5e, 26
#: calls in one program over 256 slots of 16 x 5,120 took 310-325 us a call
#: at every choice of (8-32, 1,280-5,120), this one 321 (PERF.md)
STEP_BLOCKS = (16, 2560)


def _block(total: int, most: int, unit: int) -> int:
    """The largest multiple of ``unit`` that divides ``total`` and is at most
    ``most``; ``total`` itself where there is none (a block of the whole
    axis)."""
    for size in range(min(total, most) // unit * unit, 0, -unit):
        if total % size == 0:
            return size
    return total


def _column(eye, row):
    """A ``[1, n]`` row as an ``[n, 1]`` column: broadcast down the rows of
    the ``[n, n]`` identity and summed along the lanes."""
    return jnp.sum(eye * row, axis=1, keepdims=True)


def _advance(h, a, delta, x, b, c, eye):
    """One position of the recurrence for a block of channels: ``h`` and
    ``a`` ``[n, channels]``, ``delta`` and ``x`` ``[1, channels]``, ``b`` and
    ``c`` ``[1, n]``.  Returns the new state and ``sum_n C[n] h[n]``."""
    h = jnp.exp(a * delta) * h + _column(eye, b) * (delta * x)
    return h, jnp.sum(_column(eye, c) * h, axis=0, keepdims=True)


def _identity(n):
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    return (rows == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(F32)


# ---------------------------------------------------------------- the prefill


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref,
                 state_ref, *, rows):
    """One block of channels over one block of ``rows`` positions; the grid's
    second axis (positions) runs in order and the state is carried in
    ``state_ref`` from one block of positions to the next."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _start():
        state_ref[...] = jnp.zeros_like(state_ref)

    a, d = a_ref[...], d_ref[...]
    eye = _identity(a.shape[0])

    def tile(j, h):
        """A tile of eight positions: loaded and stored whole, advanced one
        row at a time."""
        at = pl.ds(pl.multiple_of(j * _SUBLANES, _SUBLANES), _SUBLANES)
        x, dt, b, c = (ref[at, :] for ref in (x_ref, dt_ref, b_ref, c_ref))
        ys = []
        for i in range(_SUBLANES):
            h, y = _advance(h, a, dt[i:i + 1], x[i:i + 1], b[i:i + 1],
                            c[i:i + 1], eye)
            ys.append(y)
        y_ref[at, :] = jnp.concatenate(ys, axis=0) + d * x
        return h

    h = jax.lax.fori_loop(0, rows // _SUBLANES, tile, state_ref[...])
    state_ref[...] = h

    @pl.when(t == pl.num_programs(1) - 1)
    def _end():
        h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan(x, delta, b, c, a, d, *, interpret=False):
    """The recurrence over one sequence from ``h = 0``: ``x`` and ``delta``
    ``[length, channels]``, ``b`` and ``c`` ``[length, n]``, ``a [n,
    channels]``, ``d [channels]``, all float32.  Returns ``(y [length,
    channels], h [n, channels])``: the outputs with ``D x`` added, and the
    state after the last position.  A grid step takes at most
    :data:`SCAN_BLOCKS`, each cut to a divisor of its axis.  ``interpret``: a ``pltpu.InterpretParams`` runs the TPU interpreter."""
    length, width = x.shape
    n = a.shape[0]
    cb = _block(width, SCAN_BLOCKS[0], _LANES)
    tb = _block(length, SCAN_BLOCKS[1], _SUBLANES)
    rows = lambda minor, by: pl.BlockSpec((tb, minor), by)
    chan = lambda lead: pl.BlockSpec((lead, cb), lambda ci, t: (0, ci))
    return pl.pallas_call(
        functools.partial(_scan_kernel, rows=tb),
        grid=(width // cb, length // tb),
        in_specs=[rows(cb, lambda ci, t: (t, ci)),
                  rows(cb, lambda ci, t: (t, ci)),
                  rows(n, lambda ci, t: (t, 0)),
                  rows(n, lambda ci, t: (t, 0)),
                  chan(n), chan(1)],
        out_specs=[rows(cb, lambda ci, t: (t, ci)), chan(n)],
        out_shape=[jax.ShapeDtypeStruct((length, width), F32),
                   jax.ShapeDtypeStruct((n, width), F32)],
        scratch_shapes=[pltpu.VMEM((n, cb), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(*(t.astype(F32) for t in (x, delta, b, c, a)),
      d.astype(F32).reshape(1, width))


# ------------------------------------------------------------------- the step


def _step_kernel(live_ref, h_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                 ho_ref, y_ref, *, slots):
    """``slots`` slots of one block of channels, one position each."""
    g = pl.program_id(0)
    a, d = a_ref[...], d_ref[...]
    eye = _identity(a.shape[0])
    for i in range(slots):
        h = h_ref[i]
        x = x_ref[i:i + 1, :]
        new, y = _advance(h, a, dt_ref[i:i + 1, :], x, b_ref[i:i + 1, :],
                          c_ref[i:i + 1, :], eye)
        ho_ref[i] = jnp.where(live_ref[g * slots + i] != 0, new, h)
        y_ref[i:i + 1, :] = y + d * x


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step(state, x, delta, b, c, a, d, live, *, interpret=False):
    """One position of every slot: ``state [rows >= slots, n, channels]``
    float32 (a slot's state at its index), ``x`` and ``delta [slots,
    channels]``, ``b`` and ``c [slots, n]``, ``a [n, channels]``, ``d
    [channels]``, ``live [slots]``.  Returns ``(state, y [slots, channels])``:
    the store with each live slot's state advanced in place and every other
    row as it was, and every slot's output with ``D x`` added.  A grid step
    takes at most :data:`STEP_BLOCKS`."""
    slots, width = x.shape
    n = a.shape[0]
    sb = _block(slots, STEP_BLOCKS[0], _SUBLANES)
    cb = _block(width, STEP_BLOCKS[1], _LANES)
    store = pl.BlockSpec((sb, n, cb), lambda g, ci, live: (g, 0, ci))
    rows = lambda minor, by: pl.BlockSpec((sb, minor), by)
    chan = lambda lead: pl.BlockSpec((lead, cb), lambda g, ci, live: (0, ci))
    # in and out, each twice for the pipeline, with room to spare
    vmem = 8 * sb * n * cb * 4 + (16 << 20)
    return pl.pallas_call(
        functools.partial(_step_kernel, slots=sb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots // sb, width // cb),
            in_specs=[store,
                      rows(cb, lambda g, ci, live: (g, ci)),
                      rows(cb, lambda g, ci, live: (g, ci)),
                      rows(n, lambda g, ci, live: (g, 0)),
                      rows(n, lambda g, ci, live: (g, 0)),
                      chan(n), chan(1)],
            out_specs=[store, rows(cb, lambda g, ci, live: (g, ci))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((slots, width), F32)],
        # the store is written where it lies: argument 1 (after ``live``)
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ssm_step",
    )(live.astype(jnp.int32), state, *(t.astype(F32) for t in (
        x, delta, b, c, a)), d.astype(F32).reshape(1, width))


# ------------------------------------------------- the plain path and the choice


def plain_scan(x, delta, b, c, a, d):
    """:func:`ssm_scan` as a ``lax.scan`` over the positions, in
    ``jax.numpy``: what every backend but the TPU runs."""
    a = a.astype(F32)

    def position(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(a * dt_t[None]) * h + b_t[:, None] * (dt_t * x_t)[None]
        return h, jnp.sum(c_t[:, None] * h, axis=0)

    x = x.astype(F32)
    h, y = jax.lax.scan(position, jnp.zeros(a.shape, F32),
                        (x, delta.astype(F32), b.astype(F32), c.astype(F32)))
    return y + d.astype(F32) * x, h


def plain_step(state, x, delta, b, c, a, d, live):
    """:func:`ssm_step` in ``jax.numpy``."""
    slots = x.shape[0]
    x, delta = x.astype(F32), delta.astype(F32)
    h = state[:slots]
    new = (jnp.exp(a.astype(F32)[None] * delta[:, None]) * h
           + b.astype(F32)[:, :, None] * (delta * x)[:, None])
    y = jnp.sum(c.astype(F32)[:, :, None] * new, axis=1) + d.astype(F32) * x
    return state.at[:slots].set(jnp.where(live[:, None, None], new, h)), y


def scan_fits(x, a) -> bool:
    """Does the compiled ``ssm_scan`` take a chunk of these shapes?  Channels
    of whole lanes and positions of whole sublane tiles."""
    length, width = x.shape
    return width % _LANES == 0 and length % _SUBLANES == 0 and a.shape[
        1] == width


def step_fits(state, x) -> bool:
    """Does the compiled ``ssm_step`` take these?  A float32 store, channels
    of whole lanes, and slots in whole sublane tiles or one group."""
    slots, width = x.shape
    return (state.dtype == F32 and width % _LANES == 0
            and (slots % _SUBLANES == 0 or slots <= _SUBLANES))


def selective_scan(x, delta, b, c, a, d):
    """:func:`ssm_scan` where it is lowered for a TPU at shapes it takes,
    :func:`plain_scan` everywhere else; the same arguments and results."""
    if scan_fits(x, a):
        return jax.lax.platform_dependent(
            x, delta, b, c, a, d, default=plain_scan,
            tpu=functools.partial(ssm_scan, interpret=False))
    return plain_scan(x, delta, b, c, a, d)


def selective_step(state, x, delta, b, c, a, d, live):
    """:func:`ssm_step` where it is lowered for a TPU at shapes it takes,
    :func:`plain_step` everywhere else."""
    if step_fits(state, x):
        return jax.lax.platform_dependent(
            state, x, delta, b, c, a, d, live, default=plain_step,
            tpu=functools.partial(ssm_step, interpret=False))
    return plain_step(state, x, delta, b, c, a, d, live)
