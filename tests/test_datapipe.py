"""distkeras_tpu.datapipe — the sharded, prefetching, resumable input
pipeline (ISSUE 10 tentpole).

Pins the subsystem's four guarantees:

* **Bitwise parity** — blocks through the PrefetchRing, and whole training
  trajectories with ``prefetch>0``, are identical to the non-prefetched path
  (float32 AND the fused-bf16 host gather+cast).
* **Deterministic resume** — a run killed mid-epoch restores model +
  DataState, consumes exactly the remaining blocks of the interrupted epoch,
  and lands on the uninterrupted run's final params bit-for-bit.
* **Packing correctness** — packed segment-ID attention produces, for every
  packed segment, the logits the sequence gets alone (TransformerLM and
  StagedLM).
* **No hangs, no orphans** — producer exceptions propagate, close() always
  joins the worker thread, and the stall/depth metrics + gather spans make
  the overlap observable.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import telemetry
from distkeras_tpu.data import epoch_window_iter
from distkeras_tpu.datapipe import (
    ArraySource,
    DataState,
    MemmapSource,
    PrefetchRing,
    host_shard,
    pack_sequences,
)


@pytest.fixture
def live_telemetry(tmp_path, monkeypatch):
    """Telemetry on with clean global tracer/registry, flushes to tmp."""
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    telemetry.configure(True)
    telemetry.trace.reset()
    telemetry.metrics.reset()
    yield
    telemetry.trace.reset()
    telemetry.metrics.reset()
    telemetry.configure(None)


def _toy_blocks(seed=1, n=64, workers=2, batch=4, window=2, **kw):
    feats = np.random.default_rng(0).normal(size=(n, 4)).astype(np.float32)
    labels = (np.arange(n) % 3).astype(np.int32)
    rng = np.random.default_rng(seed) if seed is not None else None
    return epoch_window_iter(feats, labels, workers, batch, window,
                             rng=rng, **kw)


# ------------------------------------------------------------------- ring

def test_ring_blocks_bitwise_identical():
    plain = list(_toy_blocks(seed=1))
    ring = list(PrefetchRing(_toy_blocks(seed=1), depth=2))
    assert len(ring) == len(plain) > 0
    for (a, b), (c, d) in zip(plain, ring):
        assert a.tobytes() == c.tobytes()
        assert b.tobytes() == d.tobytes()


def _no_prefetch_threads():
    return not any(t.name == "datapipe-prefetch" and t.is_alive()
                   for t in threading.enumerate())


def test_ring_producer_exception_propagates_without_orphan():
    first = next(_toy_blocks())

    def bad():
        yield first
        raise RuntimeError("boom")

    ring = PrefetchRing(bad(), depth=2)
    got = next(ring)
    assert got[0].tobytes() == first[0].tobytes()
    with pytest.raises(RuntimeError, match="boom"):
        next(ring)
    # the producer thread is joined by the time the exception surfaces
    assert _no_prefetch_threads()
    # and the ring is terminal, not wedged
    with pytest.raises(StopIteration):
        next(ring)


def test_ring_close_mid_stream_joins_producer():
    ring = PrefetchRing(_toy_blocks(), depth=1)
    next(ring)
    ring.close()
    assert _no_prefetch_threads()
    ring.close()  # idempotent
    with pytest.raises(StopIteration):
        next(ring)


def test_engine_error_path_closes_ring(toy_classification):
    """run_epoch_streaming's try/finally must close the ring on ANY exit —
    here the producer's own error surfaces through the engine and the
    worker thread is still joined (no orphan to leak into the next test)."""
    from distkeras_tpu.algorithms import Downpour
    from distkeras_tpu.models import MLP, FlaxModel
    from distkeras_tpu.parallel.engine import WindowedEngine

    x, y, onehot = toy_classification
    eng = WindowedEngine(
        FlaxModel(MLP(features=(16,), num_classes=2)),
        loss="categorical_crossentropy",
        worker_optimizer=("sgd", {"learning_rate": 0.05}),
        rule=Downpour(communication_window=2),
        num_workers=4,
    )
    state = eng.init_state(jax.random.PRNGKey(0), x[:8])
    blocks = list(epoch_window_iter(x, onehot, 4, 8, 2))

    def dying_source():
        yield blocks[0]
        yield blocks[1]
        raise RuntimeError("source died")

    ring = PrefetchRing(dying_source(), depth=2)
    with pytest.raises(RuntimeError, match="source died"):
        eng.run_epoch_streaming(state, ring)
    assert _no_prefetch_threads()
    assert ring._closed.is_set()


class _SlowBlocks:
    def __init__(self, blocks, latency):
        self._blocks, self._latency = blocks, latency

    def __iter__(self):
        for b in self._blocks:
            time.sleep(self._latency)
            yield b


def test_ring_stall_metrics_and_link_warning(live_telemetry, toy_classification):
    """A throttled source through the ring: the consumer's waits land in
    ``datapipe_stall_seconds``, the depth gauge appears, and the engine's
    link-bound guardrail still fires — the ring hides latency, it must not
    hide the verdict that the source is the bottleneck."""
    from distkeras_tpu.algorithms import Downpour
    from distkeras_tpu.models import MLP, FlaxModel
    from distkeras_tpu.parallel.engine import WindowedEngine

    x, y, onehot = toy_classification
    eng = WindowedEngine(
        FlaxModel(MLP(features=(16,), num_classes=2)),
        loss="categorical_crossentropy",
        worker_optimizer=("sgd", {"learning_rate": 0.05}),
        rule=Downpour(communication_window=2),
        num_workers=4,
    )
    state = eng.init_state(jax.random.PRNGKey(0), x[:8])
    blocks = list(epoch_window_iter(x, onehot, 4, 8, 2))  # 8 windows

    # warmup epoch compiles the window program (fast source: quiet)
    state, _ = eng.run_epoch_streaming(state, PrefetchRing(iter(blocks)))
    assert not eng.last_stream_report["link_bound"]

    ring = PrefetchRing(_SlowBlocks(blocks, 0.05), depth=2)
    with pytest.warns(RuntimeWarning, match="source is the bottleneck"):
        state, _ = eng.run_epoch_streaming(state, ring)
    assert eng.last_stream_report["link_bound"]
    assert ring.stall_seconds > 0
    snap = telemetry.metrics.snapshot()
    assert snap["datapipe_stall_seconds"]["value"] > 0
    assert "datapipe_prefetch_depth" in snap


def test_ring_gather_spans_on_producer_thread(live_telemetry):
    """Overlap is observable: gather spans carry the producer thread's tid,
    distinct from the consumer's — in a merged Chrome trace they overlap
    the main thread's step spans instead of serialising with them."""
    with telemetry.trace.span("consumer_step"):
        for _ in PrefetchRing(_toy_blocks(), depth=2):
            time.sleep(0.001)
    events = telemetry.trace.export()["traceEvents"]
    gathers = [e for e in events if e["name"] == "datapipe_gather"]
    steps = [e for e in events if e["name"] == "consumer_step"]
    assert gathers and steps
    assert {e["tid"] for e in gathers}.isdisjoint({e["tid"] for e in steps})


# ----------------------------------------------------------- resume cursor

def test_start_block_yields_identical_tail():
    plain = list(_toy_blocks(seed=1))
    tail = list(_toy_blocks(seed=1, start_block=3))
    assert len(tail) == len(plain) - 3
    for (a, b), (c, d) in zip(plain[3:], tail):
        assert a.tobytes() == c.tobytes()
        assert b.tobytes() == d.tobytes()


def test_start_block_bounds_validated():
    with pytest.raises(ValueError, match="start_block"):
        list(_toy_blocks(start_block=-1))
    with pytest.raises(ValueError, match="start_block"):
        list(_toy_blocks(start_block=99))
    # == n_windows is legal: an empty tail (resume landed on the boundary)
    assert list(_toy_blocks(seed=1, start_block=len(list(_toy_blocks(seed=1))))) == []


def test_data_state_json_and_rng_roundtrip():
    rng = np.random.default_rng(7)
    rng.permutation(10)  # advance past the seed state
    ds = DataState.capture(3, rng, block_cursor=5)
    ds2 = DataState.from_json(ds.to_json())
    assert (ds2.epoch, ds2.block_cursor) == (3, 5)
    restored = ds2.restore_rng(np.random.default_rng(0))
    np.testing.assert_array_equal(restored.permutation(16), rng.permutation(16))
    # shuffle-off runs carry no rng state; restore is a no-op
    ds3 = DataState.capture(1, None)
    assert ds3.rng_state is None
    fresh = np.random.default_rng(5)
    expected = np.random.default_rng(5).permutation(8)
    np.testing.assert_array_equal(ds3.restore_rng(fresh).permutation(8), expected)


# ------------------------------------------------------------ checkpointing

def _tiny_state():
    from distkeras_tpu.algorithms import Downpour
    from distkeras_tpu.models import MLP, FlaxModel
    from distkeras_tpu.parallel.engine import WindowedEngine

    eng = WindowedEngine(
        FlaxModel(MLP(features=(4,), num_classes=2)),
        loss="categorical_crossentropy",
        worker_optimizer=("sgd", {"learning_rate": 0.05}),
        rule=Downpour(communication_window=2), num_workers=2,
    )
    x = np.zeros((4, 8), np.float32)
    return eng.init_state(jax.random.PRNGKey(0), x)


def test_data_state_sidecar_save_restore(tmp_path):
    from distkeras_tpu import checkpoint as ckpt_mod

    d = str(tmp_path)
    state = _tiny_state()
    ckpt_mod.save_checkpoint(d, state, step=2)
    ckpt_mod.wait_until_finished()
    ds = DataState.capture(1, np.random.default_rng(3), block_cursor=2)
    ckpt_mod.save_data_state(d, ds, step=2)
    got = ckpt_mod.restore_data_state(d)  # step=None -> latest
    assert (got.epoch, got.block_cursor) == (1, 2)
    assert got.rng_state == ds.rng_state
    # a step without a sidecar restores None
    assert ckpt_mod.restore_data_state(d, step=99) is None


def test_data_state_sidecar_lands_in_a_directory_not_yet_made(tmp_path):
    """The sidecar is written beside the model's *asynchronous* save, which
    may not have made the checkpoint directory yet (seen under six test
    workers: the online scheduler then retrained its window)."""
    from distkeras_tpu import checkpoint as ckpt_mod

    d = str(tmp_path / "not" / "yet")
    ds = DataState.capture(4, np.random.default_rng(1), block_cursor=7)
    path = ckpt_mod.save_data_state(d, ds, step=5)
    assert os.path.dirname(path) == d and os.path.isfile(path)
    got = ckpt_mod.restore_data_state(d, step=5)
    assert (got.epoch, got.block_cursor) == (4, 7)


def test_manager_partial_then_boundary_save_and_gc(tmp_path):
    """save_partial writes model + sidecar; the SAME step's later boundary
    save must overwrite the partial (Orbax refuses overwrites unless the
    manager knows the step is partial) and remove the stale sidecar; _gc
    collects sidecars with their steps."""
    from distkeras_tpu.checkpoint import CheckpointManager, data_state_path

    d = str(tmp_path)
    mgr = CheckpointManager(d, every=1, keep=2)
    state = _tiny_state()
    ds = DataState.capture(1, np.random.default_rng(0), block_cursor=2)
    mgr.save_partial(state, epoch=1, data_state=ds)
    mgr.wait()
    assert os.path.exists(data_state_path(d, 2))
    assert mgr.restore_data_state(2).block_cursor == 2

    # epoch 1 completes: boundary save of the same step replaces the partial
    mgr.maybe_save(state, epoch=1)
    mgr.wait()
    assert mgr.latest() == 2
    assert not os.path.exists(data_state_path(d, 2))  # stale sidecar gone
    assert mgr.restore_data_state(2) is None

    # keep=2: step 2's sidecar-bearing successors gc together
    for epoch in (2, 3, 4):
        mgr.save_partial(state, epoch=epoch,
                         data_state=DataState(epoch=epoch, block_cursor=1))
    mgr.wait()
    assert not os.path.exists(data_state_path(d, 3))  # gc'd with step 3
    assert os.path.exists(data_state_path(d, 5))


def test_fresh_manager_detects_partial_step_from_sidecar(tmp_path):
    """The resume race: a killed run's step dir exists with a cursor>0
    sidecar; a FRESH manager (new process) must treat that step as partial
    and force-overwrite at the boundary save instead of crashing on
    Orbax's destination-exists error."""
    from distkeras_tpu.checkpoint import CheckpointManager, data_state_path

    d = str(tmp_path)
    state = _tiny_state()
    m1 = CheckpointManager(d, every=1)
    m1.save_partial(state, epoch=0,
                    data_state=DataState(epoch=0, block_cursor=1))
    m1.wait()

    m2 = CheckpointManager(d, every=1)  # the resuming process
    m2.maybe_save(state, epoch=0)       # same step 1, now a boundary save
    m2.wait()
    assert m2.latest() == 1
    assert not os.path.exists(data_state_path(d, 1))


# ------------------------------------------------------------------ sources

def test_host_shard_balanced_and_total():
    n = 103
    ranges = [host_shard(n, i, 4) for i in range(4)]
    sizes = [hi - lo for lo, hi in ranges]
    assert sum(sizes) == n and max(sizes) - min(sizes) <= 1
    assert ranges[0][0] == 0 and ranges[-1][1] == n
    for (_, hi), (lo, _) in zip(ranges, ranges[1:]):
        assert hi == lo
    with pytest.raises(ValueError):
        host_shard(n, 4, 4)


def test_array_source_shards_rows():
    feats = np.arange(20, dtype=np.float32).reshape(10, 2)
    labels = np.arange(10, dtype=np.int32)
    s0 = ArraySource(feats, labels, process_index=0, process_count=2)
    s1 = ArraySource(feats, labels, process_index=1, process_count=2)
    assert len(s0) == len(s1) == 10  # global
    assert s0.local_rows + s1.local_rows == 10
    f0, _ = s0.local_arrays()
    f1, _ = s1.local_arrays()
    np.testing.assert_array_equal(np.concatenate([f0, f1]), feats)
    # unsharded keeps everything
    assert ArraySource(feats, labels, shard=False).local_rows == 10
    with pytest.raises(ValueError, match="disagree"):
        ArraySource(feats, labels[:5])


def test_source_window_iter_matches_epoch_window_iter():
    feats = np.random.default_rng(0).normal(size=(48, 3)).astype(np.float32)
    labels = (np.arange(48) % 2).astype(np.int32)
    src = ArraySource(feats, labels, shard=False)
    a = list(src.window_iter(2, 4, 2, rng=np.random.default_rng(9)))
    b = list(epoch_window_iter(feats, labels, 2, 4, 2,
                               rng=np.random.default_rng(9)))
    for (ax, ay), (bx, by) in zip(a, b):
        assert ax.tobytes() == bx.tobytes() and ay.tobytes() == by.tobytes()


def test_array_source_from_dataframe(toy_classification):
    from distkeras_tpu.frame import from_numpy

    x, y, onehot = toy_classification
    src = ArraySource.from_dataframe(from_numpy(x, onehot), shard=False)
    f, l = src.local_arrays()
    assert f.dtype == np.float32 and f.shape == x.shape
    np.testing.assert_array_equal(f, x)


def test_memmap_source_single_file_and_shards(tmp_path):
    feats = np.arange(24, dtype=np.float32).reshape(12, 2)
    labels = np.arange(12, dtype=np.int32)
    fp, lp = str(tmp_path / "f.npy"), str(tmp_path / "l.npy")
    np.save(fp, feats)
    np.save(lp, labels)

    # single file: row-range shard, zero-copy view
    s0 = MemmapSource(fp, lp, process_index=0, process_count=2)
    s1 = MemmapSource(fp, lp, process_index=1, process_count=2)
    assert len(s0) == 12
    f0, _ = s0.local_arrays()
    f1, _ = s1.local_arrays()
    np.testing.assert_array_equal(np.concatenate([f0, f1]), feats)

    # file shards: round-robin assignment
    fa, la = str(tmp_path / "fa.npy"), str(tmp_path / "la.npy")
    fb, lb = str(tmp_path / "fb.npy"), str(tmp_path / "lb.npy")
    np.save(fa, feats[:5]); np.save(la, labels[:5])
    np.save(fb, feats[5:]); np.save(lb, labels[5:])
    m0 = MemmapSource([fa, fb], [la, lb], process_index=0, process_count=2)
    m1 = MemmapSource([fa, fb], [la, lb], process_index=1, process_count=2)
    assert len(m0) == 12 and m0.local_rows == 5 and m1.local_rows == 7
    with pytest.raises(ValueError, match="pair up"):
        MemmapSource([fa, fb], [la])
    with pytest.raises(ValueError, match="zero of"):
        MemmapSource([fa, fb], [la, lb], process_index=2, process_count=3)


# ------------------------------------------------------------------ packing

def test_pack_sequences_layout_and_efficiency():
    seqs = [np.arange(1, n + 1) for n in (5, 3, 7, 2, 4)]
    pb = pack_sequences(seqs, 8)
    assert pb.n_sequences == 5 and pb.total_tokens == 21
    assert pb.tokens.shape[1] == 8
    assert pb.efficiency == pytest.approx(21 / pb.tokens.size)
    assert pb.model_inputs().shape == pb.tokens.shape + (2,)
    # every sequence appears exactly once, contiguous, with per-segment
    # positions restarting at 0 and 1-based segment ids (0 = pad)
    found = 0
    for r in range(pb.tokens.shape[0]):
        segs = pb.segment_ids[r]
        assert segs[segs != 0].min(initial=99) >= 1
        for seg in range(1, segs.max() + 1):
            sel = segs == seg
            toks = pb.tokens[r][sel]
            match = [s for s in seqs if len(s) == len(toks)
                     and (s == toks).all()]
            assert match, (r, seg, toks)
            np.testing.assert_array_equal(pb.positions[r][sel],
                                          np.arange(sel.sum()))
            # derived labels: next token within the segment, -1 at its tail
            labs = pb.labels[r][sel]
            np.testing.assert_array_equal(labs[:-1], toks[1:])
            assert labs[-1] == -1
            found += 1
    assert found == 5
    # pads carry -1 labels
    assert (pb.labels[pb.segment_ids == 0] == -1).all()


def test_pack_sequences_deterministic():
    rng = np.random.default_rng(2)
    seqs = [rng.integers(1, 9, size=m) for m in rng.integers(1, 17, size=40)]
    a = pack_sequences(seqs, 16)
    b = pack_sequences([s.copy() for s in seqs], 16)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_array_equal(a.segment_ids, b.segment_ids)


def test_pack_sequences_explicit_labels_and_errors():
    seqs = [np.array([1, 2, 3]), np.array([4, 5])]
    labels = [np.array([10, 20, 30]), np.array([40, 50])]
    pb = pack_sequences(seqs, 4, labels=labels)
    row0 = pb.labels[pb.segment_ids != 0]
    assert set(row0.tolist()) == {10, 20, 30, 40, 50}

    with pytest.raises(ValueError, match="width"):
        pack_sequences(seqs, 0)
    with pytest.raises(ValueError, match="no sequences"):
        pack_sequences([], 8)
    with pytest.raises(ValueError, match="empty sequence"):
        pack_sequences([np.array([1]), np.array([])], 8)
    with pytest.raises(ValueError, match="exceeds pack width"):
        pack_sequences([np.arange(9)], 8)
    with pytest.raises(ValueError, match="label"):
        pack_sequences(seqs, 8, labels=labels[:1])
    with pytest.raises(ValueError, match="tokens vs"):
        pack_sequences(seqs, 8, labels=[labels[0], labels[1][:1]])


def _packed_batch():
    seqs = [np.arange(1, n + 1) for n in (5, 3, 7, 2, 4)]
    return pack_sequences(seqs, 8)


def test_packed_transformer_lm_matches_unpacked():
    """The acceptance bar: packed segment-ID attention logits equal the
    per-sequence unpacked attention for every segment."""
    from distkeras_tpu.models.transformer import TransformerLM

    pb = _packed_batch()
    mi = jnp.asarray(pb.model_inputs())
    packed = TransformerLM(vocab_size=16, dim=32, heads=2, num_layers=2,
                           max_len=32, packed=True)
    plain = TransformerLM(vocab_size=16, dim=32, heads=2, num_layers=2,
                          max_len=32)
    # the packed model's param tree is the unpacked one's (the channel split
    # happens before any parameterised op) — parity via shared params
    params = packed.init(jax.random.PRNGKey(0), mi)["params"]
    packed_logits = np.asarray(packed.apply({"params": params}, mi))
    checked = 0
    for r in range(pb.tokens.shape[0]):
        for seg in range(1, int(pb.segment_ids[r].max()) + 1):
            sel = pb.segment_ids[r] == seg
            alone = plain.apply(
                {"params": params}, jnp.asarray(pb.tokens[r][sel][None]))
            np.testing.assert_allclose(
                np.asarray(alone[0]), packed_logits[r][sel], atol=2e-5)
            checked += 1
    assert checked == pb.n_sequences


def test_packed_staged_lm_matches_unpacked():
    from distkeras_tpu.models.staged import StagedLM

    pb = _packed_batch()
    mi = jnp.asarray(pb.model_inputs())
    packed = StagedLM(vocab_size=16, dim=32, heads=2, num_stages=2,
                      blocks_per_stage=1, max_len=32, packed=True)
    plain = StagedLM(vocab_size=16, dim=32, heads=2, num_stages=2,
                     blocks_per_stage=1, max_len=32)
    params, mstate = packed.init(jax.random.PRNGKey(1), mi)
    packed_logits, _ = packed.apply(params, mstate, mi)
    packed_logits = np.asarray(packed_logits)
    for r in range(pb.tokens.shape[0]):
        for seg in range(1, int(pb.segment_ids[r].max()) + 1):
            sel = pb.segment_ids[r] == seg
            alone, _ = plain.apply(params, mstate,
                                   jnp.asarray(pb.tokens[r][sel][None]))
            np.testing.assert_allclose(
                np.asarray(alone[0]), packed_logits[r][sel], atol=2e-5)


def test_masked_token_crossentropy_ignores_negative_labels():
    from distkeras_tpu.ops.losses import get_loss

    loss = get_loss("masked_token_crossentropy")
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 6, 8)).astype(np.float32))
    labels = np.array([[1, 2, 3, -1, -1, -1], [4, 5, -1, -1, -1, -1]])
    got = float(loss(logits, jnp.asarray(labels)))
    # reference: plain token CE over only the real positions
    import optax

    per = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(jnp.asarray(labels), 0))
    mask = labels >= 0
    want = float((np.asarray(per) * mask).sum() / mask.sum())
    assert got == pytest.approx(want, rel=1e-6)
    # all-masked batch: finite zero, not NaN
    assert float(loss(logits, jnp.full_like(jnp.asarray(labels), -1))) == 0.0
    assert get_loss("packed_crossentropy") is not None  # alias resolves


# --------------------------------------------------- trainer-level parity

def _lm_df(n=256, d=8):
    from distkeras_tpu.frame import DataFrame

    g = np.random.default_rng(0)
    x = g.normal(size=(n, d)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    onehot = np.eye(2, dtype=np.float32)[y]
    return DataFrame({"features": list(x), "label": list(onehot)})


def _mlp():
    from distkeras_tpu.models import MLP, FlaxModel

    return FlaxModel(MLP(features=(16,), num_classes=2))


def _downpour(**kw):
    import distkeras_tpu as dk

    base = dict(num_workers=8, batch_size=4, num_epoch=2,
                communication_window=4, streaming=True, seed=3)
    base.update(kw)
    return dk.DOWNPOUR(_mlp(), "categorical_crossentropy", "sgd", **base)


def _assert_trees_bitwise(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), msg


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_trainer_prefetch_trajectory_bitwise(compute_dtype):
    """prefetch>0 (ring + producer-thread device put) reproduces the
    unprefetched streaming trajectory bit-for-bit — float32 and the fused
    bf16 host gather+cast."""
    df = _lm_df()
    kw = {} if compute_dtype is None else {"compute_dtype": compute_dtype}
    p0 = _downpour(prefetch=0, **kw).train(df, shuffle=True).params
    p2 = _downpour(prefetch=2, **kw).train(df, shuffle=True).params
    _assert_trees_bitwise(p0, p2, f"prefetch diverged ({compute_dtype})")


def test_mid_epoch_kill_resume_bitwise(tmp_path, monkeypatch):
    """The resume acceptance bar: kill a run mid-epoch (after a block
    checkpoint), restore model + DataState in a fresh trainer, consume
    exactly the remaining blocks, and land on the uninterrupted run's final
    params bit-for-bit."""
    import distkeras_tpu.data as data_mod
    from distkeras_tpu.checkpoint import latest_step, restore_data_state

    df = _lm_df()

    def mk(ckdir, **kw):
        return _downpour(num_epoch=3, communication_window=2, prefetch=2,
                         checkpoint_dir=ckdir, checkpoint_blocks=2, **kw)

    dir_a, dir_b = str(tmp_path / "a"), str(tmp_path / "b")
    p_uninterrupted = mk(dir_a).train(df, shuffle=True).params

    # 4 blocks/epoch; kill the SECOND epoch's iterator at block 3 — after
    # the cursor-2 partial save, before the epoch completes
    orig_iter = data_mod.epoch_window_iter
    calls = {"n": 0}

    def killing_iter(*a, **kw):
        calls["n"] += 1
        inner = orig_iter(*a, **kw)
        if calls["n"] == 2:
            def gen():
                for i, blk in enumerate(inner):
                    if i == 3:
                        raise RuntimeError("simulated preemption")
                    yield blk
            return gen()
        return inner

    monkeypatch.setattr(data_mod, "epoch_window_iter", killing_iter)
    with pytest.raises(RuntimeError, match="preemption"):
        mk(dir_b).train(df, shuffle=True)
    monkeypatch.setattr(data_mod, "epoch_window_iter", orig_iter)

    ds = restore_data_state(dir_b)
    assert ds is not None
    assert (ds.epoch, ds.block_cursor) == (1, 2)
    assert ds.rng_state is not None
    assert latest_step(dir_b) == 2  # partial step_2 (epoch 1 in flight)

    p_resumed = mk(dir_b, resume=True).train(df, shuffle=True).params
    _assert_trees_bitwise(p_uninterrupted, p_resumed,
                          "resumed trajectory diverged")


def test_checkpoint_blocks_requires_streaming():
    import distkeras_tpu as dk

    with pytest.raises(ValueError, match="streaming"):
        dk.DOWNPOUR(_mlp(), "categorical_crossentropy", "sgd",
                    num_workers=2, checkpoint_blocks=2)
    with pytest.raises(ValueError, match="prefetch"):
        dk.DOWNPOUR(_mlp(), "categorical_crossentropy", "sgd",
                    num_workers=2, streaming=True, prefetch=-1)
