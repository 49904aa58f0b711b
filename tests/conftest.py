"""Test configuration: fake an 8-device CPU mesh before any backend init.

This is the rebuild's analogue of the reference's Spark ``local[N]`` mode
(SURVEY.md §4): the full distributed protocol runs on one machine by making
XLA expose N host devices, so every collective path (commit psums, center
replication, staleness clocks) is exercised without TPU hardware.

The suite pins the CPU through ``jax.config`` before the first backend query,
so it runs the same whatever ``JAX_PLATFORMS`` the caller's shell has (a
machine with a chip attached included): these tests must never take the chip.
"""

import os

os.environ.setdefault("KERAS_BACKEND", "jax")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture
def harness():
    """``benchmark/harness.py`` as a run imports it: the checkout and
    ``benchmark/`` on ``sys.path`` for the test, so that the readers it loads
    by file find ``flops``, ``harness`` and ``tracelib`` beside them."""
    added = [p for p in (ROOT, BENCH) if p not in sys.path]
    sys.path[:0] = added
    import harness

    yield harness
    for p in added:
        sys.path.remove(p)


@pytest.fixture(autouse=True)
def _telemetry_files_to_tmp(tmp_path, monkeypatch):
    """The CI matrix runs the whole suite with DISTKERAS_TELEMETRY=1; keep
    each test's flush() output (trace_*.json / metrics_*.jsonl) out of the
    repo checkout unless a test points the dir somewhere itself."""
    if os.environ.get("DISTKERAS_TELEMETRY") and not os.environ.get(
            "DISTKERAS_TELEMETRY_DIR"):
        monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    yield


@pytest.fixture(scope="session")
def toy_classification():
    """Small linearly-separable 2-class problem: fast convergence checks."""
    rng = np.random.default_rng(0)
    n = 512
    x = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8,))
    y = (x @ w > 0).astype(np.int32)
    onehot = np.zeros((n, 2), np.float32)
    onehot[np.arange(n), y] = 1.0
    return x, y, onehot


def toy_text(n=128, seq=16, vocab=50, seed=0):
    """Token-classification toy task shared by the parallelism test files:
    class = whether token 7 appears more often than token 3 (needs the
    whole sequence, so attention/pipelines must actually work)."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vocab, size=(n, seq)).astype(np.int32)
    y = ((x == 7).sum(1) > (x == 3).sum(1)).astype(np.int32)
    return x, y, np.eye(2, dtype=np.float32)[y]


def epoch_data(x, onehot, num_workers, n_windows, window, batch):
    """Tile (x, onehot) into the engines' epoch layout
    [workers, windows, window, batch, ...]."""
    n_need = num_workers * n_windows * window * batch
    reps = -(-n_need // len(x))
    xs = np.tile(x, (reps, 1))[:n_need].reshape(
        num_workers, n_windows, window, batch, -1)
    ys = np.tile(onehot, (reps, 1))[:n_need].reshape(
        num_workers, n_windows, window, batch, -1)
    return xs, ys
