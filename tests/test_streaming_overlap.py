"""The streaming path's double-buffering claim, MEASURED.

``run_epoch_streaming`` is designed so the next block's host gather/transfer
overlaps the current block's device compute (prefetch + delayed
block_until_ready backpressure).  Round 3 proved the trajectory is
bit-identical but never measured the overlap; this test does, on the CPU
mesh, with a *sleep*-throttled source — sleeping burns no CPU, so on the
shared 1-core host the overlap between source latency and device compute is
genuine, not a scheduling artifact.

Protocol: calibrate per-window compute wall from a source with zero
latency, then stream with per-window source latency equal to that compute
time.  Serial execution would cost ~(sleep + compute) per window; a
double-buffered pipeline costs ~max(sleep, compute).  With sleep == compute
the serial/overlap ratio is ~2x, so asserting wall < 78% of the serial
estimate discriminates cleanly while tolerating host jitter.

Sizing note: only *device compute* overlaps the source; the synchronous
per-dispatch host work (~20 ms of jit-call machinery on this box) does not.
The model/window here is sized so compute per window is ~10x the dispatch
cost — the regime streaming is for (on a chip the imbalance is larger
still: a dispatch is milliseconds, a window as large as one likes).
"""

import time

import jax
import numpy as np

from distkeras_tpu.algorithms import Downpour
from distkeras_tpu.models import MLP, FlaxModel
from distkeras_tpu.parallel.engine import WindowedEngine

WORKERS, WINDOW, BATCH, DIM, N_WINDOWS = 4, 8, 64, 512, 6


def _blocks():
    rng = np.random.default_rng(0)
    out = []
    for _ in range(N_WINDOWS):
        xs = rng.normal(size=(WORKERS, WINDOW, BATCH, DIM)).astype(np.float32)
        ys = rng.integers(0, 2, size=(WORKERS, WINDOW, BATCH)).astype(np.int32)
        out.append((xs, ys))
    return out


class _ThrottledIter:
    """Yields pre-built blocks after a fixed latency, tracking total sleep."""

    def __init__(self, blocks, latency):
        self.blocks = blocks
        self.latency = latency
        self.total_sleep = 0.0

    def __iter__(self):
        for b in self.blocks:
            time.sleep(self.latency)
            self.total_sleep += self.latency
            yield b


def test_streaming_overlaps_source_latency_with_compute():
    engine = WindowedEngine(
        FlaxModel(MLP(features=(DIM, DIM), num_classes=2)),
        "categorical_crossentropy", ("sgd", {"learning_rate": 0.05}),
        Downpour(communication_window=WINDOW), num_workers=WORKERS,
        metrics=(),
    )
    blocks = _blocks()
    x0 = blocks[0][0][0, 0]
    state = engine.init_state(jax.random.PRNGKey(0), x0)

    # warm up: compile the n_windows=1 program outside any timed region
    state, _ = engine.run_epoch_streaming(state, iter(blocks))
    jax.block_until_ready(state.center_params)

    # Other test workers on the same cores slow the timed stream and hide the
    # overlap (stream/serial is ~0.70 on an idle box, 0.89 was seen under
    # -n 6), so the claim is held to the first of a few attempts that shows
    # it, each with its own calibration.
    for attempt in range(3):
        # calibrate: compute-only wall (zero source latency)
        t0 = time.perf_counter()
        state, _ = engine.run_epoch_streaming(state, iter(blocks))
        jax.block_until_ready(state.center_params)
        wall_compute = time.perf_counter() - t0
        per_window = wall_compute / N_WINDOWS

        # stream with source latency == per-window compute
        src = _ThrottledIter(blocks, per_window)
        t0 = time.perf_counter()
        state, _ = engine.run_epoch_streaming(state, src)
        jax.block_until_ready(state.center_params)
        wall_stream = time.perf_counter() - t0

        serial_estimate = src.total_sleep + wall_compute
        overlap_efficiency = (serial_estimate - wall_stream) / src.total_sleep
        print(
            f"attempt {attempt}: compute {wall_compute:.3f}s, sleep "
            f"{src.total_sleep:.3f}s, stream {wall_stream:.3f}s, overlap "
            f"efficiency {overlap_efficiency:.2f}"
        )
        # a serial pipeline would land at ~serial_estimate; double buffering
        # at ~max(sleep, compute) = ~serial/2.  0.78 splits the two.
        if wall_stream < 0.78 * serial_estimate:
            return
    raise AssertionError(
        f"no overlap in 3 attempts; last: stream {wall_stream:.3f}s vs serial "
        f"{serial_estimate:.3f}s (compute {wall_compute:.3f}s + "
        f"sleep {src.total_sleep:.3f}s)"
    )


def test_streaming_throttled_trajectory_unchanged():
    """Backpressure/overlap must not change the math: a throttled source
    yields the bit-identical trajectory of an unthrottled one."""
    def run(throttle):
        engine = WindowedEngine(
            FlaxModel(MLP(features=(32,), num_classes=2)),
            "categorical_crossentropy", ("sgd", {"learning_rate": 0.05}),
            Downpour(communication_window=WINDOW), num_workers=WORKERS,
            metrics=(),
        )
        rng = np.random.default_rng(1)
        blocks = [
            (rng.normal(size=(WORKERS, WINDOW, BATCH, 16)).astype(np.float32),
             rng.integers(0, 2, size=(WORKERS, WINDOW, BATCH)).astype(np.int32))
            for _ in range(4)
        ]
        state = engine.init_state(jax.random.PRNGKey(0), blocks[0][0][0, 0])
        src = _ThrottledIter(blocks, 0.05) if throttle else iter(blocks)
        state, stats = engine.run_epoch_streaming(state, src)
        return (jax.tree.map(np.asarray, engine.gather_center(state)),
                np.asarray(stats["loss"]))

    center_a, loss_a = run(False)
    center_b, loss_b = run(True)
    np.testing.assert_array_equal(loss_a, loss_b)
    for a, b in zip(jax.tree.leaves(center_a), jax.tree.leaves(center_b)):
        np.testing.assert_array_equal(a, b)
