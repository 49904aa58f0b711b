"""Host-side batching: DataFrame columns -> mesh-shaped epoch arrays.

The reference streams partition row-iterators into per-worker minibatch loops
(``distkeras/workers.py`` minibatch iterator).  The TPU engine instead wants
the whole epoch as one statically-shaped array
``[num_workers, n_windows, window, batch, ...]`` so a single jitted
``shard_map`` program can scan it.  This module builds those arrays with
wrap-around padding (no sample dropped, matching the reference's
use-every-row behaviour) and per-epoch host-side shuffling.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

__all__ = ["epoch_arrays", "epoch_window_iter", "plan_epoch"]


def plan_epoch(n: int, num_workers: int, batch_size: int, window: int) -> Tuple[int, int]:
    """(n_windows, padded_total): smallest window grid covering all n samples."""
    window = max(1, window)
    per_step = num_workers * batch_size
    steps = max(1, -(-n // per_step))  # ceil
    n_windows = max(1, -(-steps // window))
    return n_windows, n_windows * window * per_step


def epoch_arrays(
    features: np.ndarray,
    labels: np.ndarray,
    num_workers: int,
    batch_size: int,
    window: int,
    *,
    stepwise: bool = False,
    rng: Optional[np.random.Generator] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Shuffle + wrap-pad + reshape one epoch of data.

    Uniform mode: leaves shaped ``[num_workers, n_windows, window, batch, ...]``.
    Stepwise (staleness-sim) mode: ``[num_workers, n_steps, batch, ...]``.
    """
    n = len(features)
    if n == 0:
        raise ValueError("empty dataset")
    idx = np.arange(n)
    if rng is not None:
        rng.shuffle(idx)
    n_windows, total = plan_epoch(n, num_workers, batch_size, window)
    reps = -(-total // n)
    idx = np.tile(idx, reps)[:total]
    # Gather is the host-side hot path: multithreaded native kernel when the
    # C++ library is available, bit-identical numpy fallback otherwise.
    from distkeras_tpu import native, telemetry

    with telemetry.trace.loop_span("epoch_arrays", phase="data",
                                    rows=int(total)) as span:
        xs = native.gather_rows(features, idx)
        ys = native.gather_rows(labels, idx)
        if stepwise:
            shape = (num_workers, n_windows * window, batch_size)
        else:
            shape = (num_workers, n_windows, window, batch_size)
        xs = xs.reshape(shape + features.shape[1:])
        ys = ys.reshape(shape + labels.shape[1:])
        # the count at the boundary: what the gather wrote this epoch
        span.attrs["bytes"] = int(xs.nbytes) + int(ys.nbytes)
    return xs, ys


def epoch_window_iter(
    features: np.ndarray,
    labels: np.ndarray,
    num_workers: int,
    batch_size: int,
    window: int,
    *,
    rng: Optional[np.random.Generator] = None,
    pad_to_window: bool = True,
    feature_dtype=None,
    start_block: int = 0,
):
    """Lazily yield one epoch as per-window blocks
    ``[num_workers, window, batch, ...]`` — the streaming twin of
    :func:`epoch_arrays`.

    Draws the identical shuffle from ``rng`` and emits rows in exactly the
    order ``epoch_arrays`` lays them out (asserted bit-for-bit in
    tests/test_streaming.py), but gathers only ``num_workers*window*batch``
    rows at a time, so the whole-epoch array never exists — on host or
    device.  This is the path for datasets approaching HBM size; the
    reference's analogue is Spark streaming partitions into executors
    (SURVEY.md §3.1) rather than collecting the dataset to the driver.

    ``pad_to_window=True`` wrap-pads the step count up to a window multiple
    (commit semantics need full windows — matches ``epoch_arrays``).  With
    ``pad_to_window=False`` the step count is planned at step granularity and
    the final block may be ragged: the right shape for no-commit trainers,
    where block boundaries are arbitrary and extra padded steps would change
    the trajectory.

    ``feature_dtype=bfloat16`` (with float32 features) emits each block
    through the fused native gather+cast (``native.gather_rows_bf16``):
    one pass over the data, half the bytes toward the device — the host
    half of the streaming path's compute-dtype transfer.  Value-identical
    to casting after the gather.

    ``start_block=k`` skips the first ``k`` windows by index arithmetic
    alone (no gather is paid for skipped blocks) while still drawing the
    full shuffle from ``rng`` — the datapipe resume path
    (:class:`distkeras_tpu.datapipe.DataState`): restore the RNG bit state
    captured before the epoch's shuffle, skip the consumed blocks, and the
    remaining blocks are bitwise the uninterrupted epoch's tail.
    """
    n = len(features)
    if n == 0:
        raise ValueError("empty dataset")
    idx = np.arange(n)
    if rng is not None:
        rng.shuffle(idx)
    if pad_to_window:
        n_windows, total = plan_epoch(n, num_workers, batch_size, window)
        steps = n_windows * window
    else:
        steps, total = plan_epoch(n, num_workers, batch_size, 1)
        n_windows = -(-steps // window)
    reps = -(-total // n)
    idx = np.tile(idx, reps)[:total]
    # epoch_arrays reshapes worker-major: worker k / window w covers the flat
    # slice idx2[k, w*window:(w+1)*window] below.
    idx2 = idx.reshape(num_workers, steps, batch_size)
    from distkeras_tpu import native, telemetry

    fused_bf16 = (
        feature_dtype is not None
        and np.dtype(feature_dtype).name == "bfloat16"
        and np.issubdtype(features.dtype, np.floating)
    )
    gather_x = native.gather_rows_bf16 if fused_bf16 else native.gather_rows
    start_block = int(start_block)
    if not 0 <= start_block <= n_windows:
        raise ValueError(
            f"start_block {start_block} outside this epoch's "
            f"[0, {n_windows}] window range"
        )
    for w in range(start_block, n_windows):
        block = idx2[:, w * window : (w + 1) * window]
        cur = block.shape[1]  # < window only for a ragged final block
        sel = np.ascontiguousarray(block).ravel()
        block_shape = (num_workers, cur, batch_size)
        with telemetry.trace.span("window_gather", phase="data",
                                  window=w, rows=int(sel.size)):
            xs = gather_x(features, sel).reshape(block_shape + features.shape[1:])
            ys = native.gather_rows(labels, sel).reshape(block_shape + labels.shape[1:])
        yield xs, ys
