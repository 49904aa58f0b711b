"""Runtime sanitizer tests (DISTKERAS_SANITIZE): mode resolution and the
cached-bool convention, the zero-cost pin for the disabled path
(byte-identical lowered programs), and one seeded violation per guard
proving each catches its dklint twin's target — an in-loop ``.item()``
trips the transfer guard (DK101), donated-but-live buffers are poisoned
(DK103), and off-lock mutation/inversion trips the lock watchdog (DK105).
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distkeras_tpu as dk
from distkeras_tpu import sanitizer, telemetry
from distkeras_tpu.algorithms import Downpour
from distkeras_tpu.data import epoch_arrays
from distkeras_tpu.frame import from_numpy
from distkeras_tpu.job_deployment import PunchcardServer
from distkeras_tpu.models import MLP, FlaxModel
from distkeras_tpu.parallel.engine import WindowedEngine
from distkeras_tpu.sanitizer import donation, lockwatch, runtime, transfer
from distkeras_tpu.sanitizer.lockwatch import LockOrderViolation
from distkeras_tpu.sanitizer.transfer import TransferViolation


@pytest.fixture(autouse=True)
def reset_sanitizer():
    """Sanitizer mode is process-cached (engines read it at build); leave
    every test with env-driven defaults and empty watchdog state."""
    yield
    sanitizer.configure(None)
    lockwatch.reset()
    donation.reset_stats()
    telemetry.configure(None)
    telemetry.trace.reset()
    telemetry.metrics.reset()


def _toy(n=128, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d,))
    y = (x @ w > 0).astype(np.int32)
    onehot = np.zeros((n, 2), np.float32)
    onehot[np.arange(n), y] = 1.0
    return x, onehot


def _mlp():
    return FlaxModel(MLP(features=(16,), num_classes=2))


def _engine(**kw):
    return WindowedEngine(
        _mlp(),
        loss=kw.pop("loss", "categorical_crossentropy"),
        worker_optimizer=("sgd", {"learning_rate": 0.1}),
        rule=Downpour(communication_window=2),
        num_workers=2,
        **kw,
    )


def _epoch_data(eng, x, onehot, batch=16, window=2):
    state = eng.init_state(jax.random.PRNGKey(0), x[:batch])
    xs, ys = epoch_arrays(x, onehot, eng.num_workers, batch, window)
    xs, ys = eng.shard_batches(xs, ys)
    return state, xs, ys


def _leaky_loss():
    """A loss with a deliberate in-loop host sync — the seeded violation
    DK101 flags statically and the transfer guard must catch at runtime."""
    const = jnp.asarray(2.0)

    def loss(out, y):
        scale = const.item()  # closure constant: trace-time sync, legal under v3 provenance
        return jnp.mean((out - y) ** 2) * scale

    return loss


# ------------------------------------------------------------ mode switch

def test_mode_resolution_from_env(monkeypatch):
    for raw, expect in [("", "off"), ("0", "off"), ("false", "off"),
                        ("no", "off"), ("1", "record"), ("true", "record"),
                        ("record", "record"), ("strict", "strict")]:
        sanitizer.configure(None)
        monkeypatch.setenv("DISTKERAS_SANITIZE", raw)
        assert sanitizer.mode() == expect, raw
    sanitizer.configure(None)
    monkeypatch.delenv("DISTKERAS_SANITIZE", raising=False)
    assert (sanitizer.mode(), sanitizer.enabled(), sanitizer.strict()) == (
        "off", False, False)


def test_mode_is_cached_until_reconfigured(monkeypatch):
    """The cached-bool convention: after the first read the env var is never
    consulted again, so the engines' build-time snapshot stays coherent."""
    sanitizer.configure(None)
    monkeypatch.delenv("DISTKERAS_SANITIZE", raising=False)
    assert sanitizer.mode() == "off"
    monkeypatch.setenv("DISTKERAS_SANITIZE", "strict")
    assert sanitizer.mode() == "off"  # cached
    sanitizer.configure(None)  # explicit reset re-reads
    assert sanitizer.mode() == "strict"


def test_configure_rejects_unknown_mode():
    with pytest.raises(ValueError, match="mode must be one of"):
        sanitizer.configure("paranoid")


# ----------------------------------------------------- transfer guard unit

def test_transfer_guard_strict_raises_and_names_label():
    sanitizer.configure("strict")
    const = jnp.asarray(2.0)
    x = jnp.ones(3)  # created outside the guard, like shard_batches output

    @jax.jit
    def f(a):
        return a * const.item()  # closure constant: trace-time sync, legal under v3 provenance

    with pytest.raises(TransferViolation, match="guard 'unit_label'"):
        with transfer.guard("unit_label"):
            f(x)


def test_transfer_guard_clean_program_passes_strict():
    sanitizer.configure("strict")
    x = jnp.ones(8)

    @jax.jit
    def f(a):
        return jnp.sum(a * 3.0) + jnp.arange(a.shape[0]).sum()

    with transfer.guard("clean"):
        out = f(x)  # trace + compile + execute all inside the guard
    assert float(jax.block_until_ready(out)) == pytest.approx(52.0)


def test_transfer_guard_record_counts_and_continues():
    sanitizer.configure("record")
    telemetry.metrics.reset()
    const = jnp.asarray(2.0)
    x = jnp.ones(3)

    @jax.jit
    def f(a):
        return a * const.item()  # closure constant: trace-time sync, legal under v3 provenance

    with pytest.warns(RuntimeWarning, match="sanitizer \\[transfer\\]"):
        with transfer.guard("rec"):
            out = f(x)
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(3))
    snap = telemetry.metrics.snapshot()
    assert snap["sanitizer_transfer_violations"]["value"] >= 1
    kinds_msgs = runtime.violations("transfer")
    assert kinds_msgs and "item() forces a device->host sync" in kinds_msgs[0][1]


def test_transfers_free_outside_guard_and_when_off():
    sanitizer.configure("record")
    assert jnp.asarray(2.0).item() == 2.0  # outside any guard: legal
    sanitizer.configure("off")
    with transfer.guard("noop"):
        assert jnp.asarray(3.0).item() == 3.0  # guard is a no-op when off
    assert runtime.violations() == []


# ----------------------------------------------------- donation guard unit

def test_donation_poison_deletes_live_leaves():
    sanitizer.configure("record")
    telemetry.metrics.reset()
    state = {"w": jnp.ones(4), "b": jnp.zeros(2), "n": 3}
    assert donation.poison(state, label="unit state") == 2
    assert state["w"].is_deleted() and state["b"].is_deleted()
    st = donation.stats()
    assert (st["poisoned"], st["boundaries"]) == (2, 1)
    snap = telemetry.metrics.snapshot()
    assert snap["sanitizer_donation_poisoned"]["value"] == 2
    with pytest.raises(RuntimeError):
        np.asarray(state["w"])  # the read-after-donate now fails everywhere


def test_donation_poison_is_noop_when_off():
    sanitizer.configure("off")
    state = {"w": jnp.ones(4)}
    assert donation.poison(state) == 0
    assert not state["w"].is_deleted()


# --------------------------------------------------------- lockwatch unit

def test_lock_order_inversion_detected():
    sanitizer.configure("record")
    a = lockwatch.maybe_wrap(threading.Lock(), "A")
    b = lockwatch.maybe_wrap(threading.Lock(), "B")
    with a:
        with b:
            pass
    with pytest.warns(RuntimeWarning, match="inversion"):
        with b:
            with a:
                pass
    assert any("inversion" in m for _, m in runtime.violations("lock"))


def test_off_lock_notify_and_guarded_map():
    sanitizer.configure("record")
    cv = lockwatch.maybe_wrap(threading.Condition(), "cv")
    jobs = lockwatch.guard_map({}, cv, "jobs")
    with pytest.warns(RuntimeWarning, match="without holding"):
        with pytest.raises(RuntimeError):  # stock Condition still errors too
            cv.notify_all()
    jobs_before = len(runtime.violations("lock"))
    jobs["k"] = 1  # off-lock write: recorded, mutation still applied
    assert len(runtime.violations("lock")) == jobs_before + 1
    with cv:
        jobs["k2"] = 2  # under the lock: silent
    assert len(runtime.violations("lock")) == jobs_before + 1
    assert jobs == {"k": 1, "k2": 2}


def test_lockwatch_strict_raises():
    sanitizer.configure("strict")
    cv = lockwatch.maybe_wrap(threading.Condition(), "cv2")
    with pytest.raises(LockOrderViolation, match="without holding"):
        cv.notify_all()


def test_exclusive_flags_same_direction_concurrency():
    sanitizer.configure("record")
    sock = object()
    entered = threading.Event()
    release = threading.Event()

    def holder():
        with lockwatch.exclusive(sock, "send"):
            entered.set()
            release.wait(timeout=5)

    t = threading.Thread(target=holder)
    t.start()
    try:
        assert entered.wait(timeout=5)
        with pytest.warns(RuntimeWarning, match="concurrent send"):
            with lockwatch.exclusive(sock, "send"):
                pass
        # full duplex is legal: recv while the other thread sends
        before = len(runtime.violations("lock"))
        with lockwatch.exclusive(sock, "recv"):
            pass
        assert len(runtime.violations("lock")) == before
    finally:
        release.set()
        t.join(timeout=5)


def test_disabled_path_returns_stock_objects():
    sanitizer.configure("off")
    cv = threading.Condition()
    assert lockwatch.maybe_wrap(cv, "x") is cv
    m = lockwatch.guard_map({"a": 1}, cv, "x")
    assert type(m) is dict and m == {"a": 1}
    srv = PunchcardServer(port=0)
    assert isinstance(srv._cv, threading.Condition)
    assert type(srv.jobs) is dict


def test_punchcard_jobs_mutation_off_lock_is_flagged():
    sanitizer.configure("record")
    srv = PunchcardServer(port=0)
    assert isinstance(srv._cv, lockwatch.GuardedLock)
    with pytest.warns(RuntimeWarning, match="off-lock write"):
        srv.jobs["job-1"] = {"status": "QUEUED"}
    with srv._cv:
        srv.jobs["job-2"] = {"status": "QUEUED"}  # the blessed path
    assert len(runtime.violations("lock")) == 1


# ------------------------------------------- engine integration + the pins

def _lowered_epoch_text(eng, x, onehot, batch=16, window=2):
    state, xs, ys = _epoch_data(eng, x, onehot, batch, window)
    fn = eng._make_epoch_fn(xs.shape[1], window, True, xs.ndim)
    with eng.mesh:
        return fn.lower(state, xs, ys).as_text()


def test_disabled_and_enabled_lowering_byte_identical():
    """The zero-cost pin: the sanitizer is host-side instrumentation around
    dispatch, so the lowered program must be byte-identical with the flag
    off, on, and strict — it adds ZERO traced ops."""
    x, onehot = _toy()
    sanitizer.configure("off")
    off_a = _lowered_epoch_text(_engine(), x, onehot)
    off_b = _lowered_epoch_text(_engine(), x, onehot)
    assert off_a == off_b
    sanitizer.configure("record")
    assert _lowered_epoch_text(_engine(), x, onehot) == off_a
    sanitizer.configure("strict")
    assert _lowered_epoch_text(_engine(), x, onehot) == off_a


def test_engine_caches_flag_at_build():
    sanitizer.configure("off")
    eng = _engine()
    assert eng._sanitize is False
    sanitizer.configure("record")
    assert eng._sanitize is False  # snapshot taken at build, like _dynamics
    assert _engine()._sanitize is True


def test_clean_epoch_passes_strict_and_poisons_donated_state():
    sanitizer.configure("strict")
    x, onehot = _toy()
    eng = _engine()
    state0, xs, ys = _epoch_data(eng, x, onehot)
    state1, stats = eng.run_epoch(state0, xs, ys)
    assert np.all(np.isfinite(np.asarray(stats["loss"])))
    # the donated input state is poisoned at the step boundary: a stale read
    # now fails on CPU exactly as it would on a donating TPU backend
    leaves = [l for l in jax.tree.leaves(state0) if isinstance(l, jax.Array)]
    assert leaves and all(l.is_deleted() for l in leaves)
    assert donation.stats()["boundaries"] >= 1
    assert runtime.violations() == []


# ------------------------------------------------------- trainer seeded runs

def test_strict_trainer_raises_on_seeded_item_and_names_span():
    """The acceptance smoke: DISTKERAS_SANITIZE=strict turns a seeded
    in-loop ``.item()`` (DK101's target) into a raise that names the
    enclosing telemetry span."""
    telemetry.configure(True)  # spans on, so the violation is attributed
    sanitizer.configure("strict")
    x, onehot = _toy()
    t = dk.DOWNPOUR(_mlp(), loss=_leaky_loss(),
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=2, batch_size=16, num_epoch=1,
                    communication_window=2, seed=7)
    with pytest.raises(TransferViolation, match="span 'dispatch'") as exc:
        t.train(from_numpy(x, onehot))
    assert "hot loop" in str(exc.value)


def test_record_trainer_counts_seeded_item_and_warns():
    sanitizer.configure("record")
    telemetry.metrics.reset()
    x, onehot = _toy()
    t = dk.DOWNPOUR(_mlp(), loss=_leaky_loss(),
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=2, batch_size=16, num_epoch=1,
                    communication_window=2, seed=7)
    with pytest.warns(RuntimeWarning, match="sanitizer"):
        t.train(from_numpy(x, onehot))  # completes despite the violation
    snap = telemetry.metrics.snapshot()
    assert snap["sanitizer_transfer_violations"]["value"] >= 1
    assert runtime.violations("transfer")
