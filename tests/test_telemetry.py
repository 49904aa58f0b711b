"""Telemetry subsystem tests: span nesting + thread safety, histogram
bucketing, Chrome-trace / Prometheus golden files, the daemon ``metrics``
verb round-trip, the disabled-path overhead pin, ScalarLogger lifecycle,
and an end-to-end smoke train that must write a Perfetto-loadable trace
whose epoch-grain spans nest under their epoch."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import telemetry
from distkeras_tpu.frame import from_numpy
from distkeras_tpu.job_deployment import Job, PunchcardServer
from distkeras_tpu.models import MLP, FlaxModel
from distkeras_tpu.telemetry.metrics import Registry
from distkeras_tpu.telemetry.profiler import ProfilerHook
from distkeras_tpu.telemetry.trace import NOOP_SPAN, Tracer
from distkeras_tpu.utils.tb import ScalarLogger

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")


@pytest.fixture(autouse=True)
def clean_telemetry(tmp_path, monkeypatch):
    """Each test starts enabled with empty global tracer/registry and leaves
    the process env-driven again.  Any flush() (the trainers do one per fit)
    lands in tmp_path, never the checkout."""
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    telemetry.configure(True)
    telemetry.trace.reset()
    telemetry.metrics.reset()
    yield
    telemetry.trace.reset()
    telemetry.metrics.reset()
    telemetry.configure(None)


def fake_clock():
    """Deterministic clock: 0.0, 1.0, 2.0, ... — one tick per call."""
    t = {"v": -1.0}

    def clock():
        t["v"] += 1.0
        return t["v"]

    return clock


# ------------------------------------------------------------------- spans

def test_span_nesting_parent_chain_and_containment():
    tr = Tracer(clock=fake_clock(), pid=0)
    with tr.span("epoch", epoch=0):
        with tr.span("window"):
            with tr.span("commit"):
                pass
    evs = {e["name"]: e for e in tr.export()["traceEvents"]}
    assert evs["epoch"]["args"] == {"epoch": 0}
    assert evs["window"]["args"]["parent"] == "epoch"
    assert evs["commit"]["args"]["parent"] == "window"
    for child, parent in (("window", "epoch"), ("commit", "window")):
        c, p = evs[child], evs[parent]
        assert p["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"]


def test_sibling_spans_share_parent_and_do_not_nest():
    tr = Tracer(clock=fake_clock(), pid=0)
    with tr.span("epoch"):
        with tr.span("a"):
            pass
        with tr.span("b"):
            pass
    evs = {e["name"]: e for e in tr.export()["traceEvents"]}
    assert evs["a"]["args"]["parent"] == "epoch"
    assert evs["b"]["args"]["parent"] == "epoch"
    # siblings are disjoint in time
    assert evs["a"]["ts"] + evs["a"]["dur"] <= evs["b"]["ts"]


def test_span_thread_safety():
    tr = Tracer()
    n_threads, n_spans = 8, 50
    # all threads alive at once, else the OS reuses thread idents and the
    # distinct-tid assertion below would be vacuous
    barrier = threading.Barrier(n_threads)

    def work(i):
        barrier.wait()
        for k in range(n_spans):
            with tr.span(f"outer_{i}", k=k):
                with tr.span(f"inner_{i}"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.export()["traceEvents"]
    assert len(evs) == n_threads * n_spans * 2
    assert len({e["tid"] for e in evs}) == n_threads
    assert all(e["dur"] >= 0 for e in evs)
    # nesting is tracked per thread: every inner span's parent is its own
    # thread's outer span, never another thread's
    for e in evs:
        if e["name"].startswith("inner_"):
            assert e["args"]["parent"] == "outer_" + e["name"].split("_")[1]


def test_exported_trace_is_json_loadable(tmp_path):
    with telemetry.trace.span("epoch"):
        pass
    path = telemetry.trace.write(str(tmp_path / "trace.json"))
    payload = json.load(open(path))
    assert payload["traceEvents"][0]["name"] == "epoch"
    assert payload["traceEvents"][0]["ph"] == "X"


def test_disabled_span_is_shared_noop_and_cheap():
    telemetry.configure(False)
    s1 = telemetry.trace.span("x")
    s2 = telemetry.trace.span("y", phase="step", attr=1)
    assert s1 is s2 is NOOP_SPAN
    with s1:
        pass  # records nothing
    telemetry.configure(True)
    assert telemetry.trace.export()["traceEvents"] == []

    # Overhead pin: the disabled path must stay within a small constant
    # factor of a plain dict lookup (it is: one cached-bool check + returning
    # a shared object).  Generous bound + absolute floor to stay unflaky on
    # loaded CI machines.
    telemetry.configure(False)
    n = 20000
    d = {"k": 1}
    t0 = time.perf_counter()
    for _ in range(n):
        d.get("k")
    dict_t = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        telemetry.trace.span("x")
    span_t = time.perf_counter() - t0
    assert span_t < max(100 * dict_t, 0.05), (
        f"disabled span() cost {span_t:.4f}s vs dict lookup {dict_t:.4f}s"
    )


# ----------------------------------------------------------------- metrics

def test_histogram_bucketing_le_semantics():
    reg = Registry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 2.5, 100.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(104.0)
    # cumulative le buckets: 1.0 counts into le=1, 2.5 into le=5, 100 -> +Inf
    assert h.cumulative() == [("1", 2), ("2", 2), ("5", 3), ("+Inf", 4)]


def test_histogram_is_bounded():
    reg = Registry()
    h = reg.histogram("lat", buckets=(0.1,))
    for _ in range(1000):
        h.observe(9e9)
    assert len(h.cumulative()) == 2  # one finite bucket + overflow, always


def test_counter_gauge_and_type_conflict():
    reg = Registry()
    reg.counter("n").inc()
    reg.counter("n").inc(2.5)
    assert reg.counter("n").value == pytest.approx(3.5)
    with pytest.raises(ValueError):
        reg.counter("n").inc(-1)
    reg.gauge("g").set(7)
    assert reg.gauge("g").value == 7.0
    with pytest.raises(TypeError):
        reg.gauge("n")  # already a counter


def test_phase_breakdown_always_has_canonical_keys():
    assert telemetry.metrics.phase_breakdown() == {
        "data": 0.0, "h2d": 0.0, "step": 0.0, "commit": 0.0,
    }
    with telemetry.trace.span("x", phase="step"):
        pass
    bd = telemetry.metrics.phase_breakdown()
    assert bd["step"] > 0.0
    assert set(bd) >= {"data", "h2d", "step", "commit"}


def test_registry_write_jsonl(tmp_path):
    telemetry.metrics.counter("c").inc(2)
    path = telemetry.metrics.write_jsonl(str(tmp_path / "m.jsonl"),
                                         extra={"run": 1})
    line = json.loads(open(path).read().splitlines()[-1])
    assert line["run"] == 1
    assert line["metrics"]["c"] == {"type": "counter", "value": 2.0}


def test_registry_to_scalar_logger_bridge(tmp_path, monkeypatch):
    monkeypatch.setattr(ScalarLogger, "_try_torch", lambda self: False)
    telemetry.metrics.counter("commits_total").inc(4)
    telemetry.metrics.histogram("lat", buckets=(1.0,)).observe(0.5)
    with ScalarLogger(str(tmp_path)) as log:
        telemetry.metrics.to_scalar_logger(log, step=3)
    rec = json.loads(open(tmp_path / "scalars.jsonl").read().splitlines()[-1])
    assert rec["step"] == 3
    assert rec["commits_total"] == 4.0
    assert rec["lat_sum"] == pytest.approx(0.5)
    assert rec["lat_count"] == 1


# ------------------------------------------------------------ golden files

def test_chrome_trace_golden():
    tr = Tracer(clock=fake_clock(), pid=0)
    with tr.span("epoch", epoch=0):
        with tr.span("window", windows=2):
            with tr.span("step", phase=None):
                pass
            with tr.span("commit"):
                pass
    golden = json.load(open(os.path.join(GOLDEN, "telemetry_trace.json")))
    assert tr.export() == golden


def test_prometheus_golden():
    reg = Registry()
    reg.counter("jax_compiles_total", help="compile events").inc(3)
    reg.gauge("samples_per_sec_per_chip").set(1234.5)
    h = reg.histogram("phase_step_seconds", help="step phase",
                      buckets=(0.001, 0.01, 0.1))
    h.observe(0.0005)
    h.observe(0.05)
    golden = open(os.path.join(GOLDEN, "telemetry_prometheus.txt")).read()
    assert reg.to_prometheus() == golden


# ---------------------------------------------------------- fleet merging

def _job_snapshots():
    """Two job snapshots with overlapping metrics and DIFFERENT histogram
    ladders — the shape the golden file pins."""
    snap1 = {
        "jobs_trained_total": {"type": "counter", "value": 3.0},
        "dynamics_grad_norm": {"type": "gauge", "value": 1.5},
        "phase_step_seconds": {"type": "histogram", "sum": 1.9, "count": 5,
                               "buckets": {"0.1": 2, "1": 5, "+Inf": 5}},
    }
    snap2 = {
        "jobs_trained_total": {"type": "counter", "value": 2.0},
        "dynamics_grad_norm": {"type": "gauge", "value": 2.5},
        "phase_step_seconds": {"type": "histogram", "sum": 6.0, "count": 4,
                               "buckets": {"0.25": 1, "1": 3, "10": 4,
                                           "+Inf": 4}},
    }
    return snap1, snap2


def test_merge_snapshots_counters_gauges_histograms():
    from distkeras_tpu.telemetry.metrics import merge_snapshots

    merged = merge_snapshots(list(_job_snapshots()))
    assert merged["jobs_trained_total"] == {"type": "counter", "value": 5.0}
    g = merged["dynamics_grad_norm"]
    assert (g["value"], g["mean"]) == (2.5, 2.0)  # max + mean across jobs
    h = merged["phase_step_seconds"]
    assert h["sum"] == pytest.approx(7.9)
    assert h["count"] == 9
    # union ladder with cumulative counts carried forward exactly: snap1
    # contributes its le=0.1 count at 0.25, its le=1 count at 10
    assert h["buckets"] == {"0.1": 2, "0.25": 3, "1": 8, "10": 9, "+Inf": 9}


def test_merge_snapshots_type_conflict_and_identity():
    from distkeras_tpu.telemetry.metrics import merge_snapshots

    snap1, _ = _job_snapshots()
    merged = merge_snapshots([snap1])
    # counters/histograms are identity; gauges always carry the fleet shape
    # (max + mean) so the schema is stable as the fleet grows
    assert merged["jobs_trained_total"] == snap1["jobs_trained_total"]
    assert merged["phase_step_seconds"] == snap1["phase_step_seconds"]
    assert merged["dynamics_grad_norm"] == {"type": "gauge", "value": 1.5,
                                            "mean": 1.5}
    assert merge_snapshots([]) == {}
    with pytest.raises(ValueError):
        merge_snapshots([snap1, {"jobs_trained_total":
                                 {"type": "gauge", "value": 1.0}}])


def test_fleet_aggregate_prometheus_golden():
    from distkeras_tpu.telemetry.metrics import (
        merge_snapshots,
        prometheus_from_snapshot,
    )

    merged = merge_snapshots(list(_job_snapshots()))
    golden = open(os.path.join(GOLDEN, "telemetry_aggregate.txt")).read()
    assert prometheus_from_snapshot(merged) == golden


# -------------------------------------------------------- daemon round-trip

@pytest.fixture
def punchcard():
    server = PunchcardServer(port=0, secret="s3cret")
    server.start()
    yield server
    server.stop()


def test_daemon_metrics_verb_roundtrip(punchcard):
    telemetry.metrics.counter("commits_total").inc(5)
    telemetry.metrics.histogram("lat", buckets=(1.0,)).observe(0.25)
    reply = Job("127.0.0.1", punchcard.port, secret="s3cret").metrics()
    assert reply["status"] == "ok"
    assert reply["enabled"] is True
    assert "commits_total 5" in reply["prometheus"]
    assert 'lat_bucket{le="1"} 1' in reply["prometheus"]
    assert reply["snapshot"]["commits_total"] == {"type": "counter", "value": 5.0}
    assert reply["snapshot"]["lat"]["count"] == 1


def test_daemon_metrics_verb_requires_secret(punchcard):
    reply = Job("127.0.0.1", punchcard.port, secret="wrong").metrics()
    assert reply["status"] == "denied"


# Jobs that report the exact snapshots the aggregate golden pins: counter 3
# + gauge 1.5 + a (0.1, 1) histogram ladder, vs counter 2 + gauge 2.5 + a
# (0.25, 1, 10) ladder.
_FLEET_JOB = """\
from distkeras_tpu import telemetry

telemetry.metrics.counter("jobs_trained_total").inc({inc})
telemetry.metrics.gauge("dynamics_grad_norm").set({gauge})
h = telemetry.metrics.histogram("phase_step_seconds", buckets={buckets})
for v in {observations}:
    h.observe(v)
telemetry.flush()
"""


def test_daemon_fleet_aggregate_roundtrip_matches_golden(punchcard, monkeypatch):
    """Acceptance: two jobs run under the daemon (each in its own telemetry
    dir), and the ``aggregate`` verb returns the merged fleet snapshot —
    byte-identical to the committed Prometheus golden."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("PYTHONPATH", repo)  # jobs run from the daemon workdir
    scripts = [
        _FLEET_JOB.format(inc=3, gauge=1.5, buckets=(0.1, 1.0),
                          observations=(0.05, 0.05, 0.3, 0.5, 1.0)),
        _FLEET_JOB.format(inc=2, gauge=2.5, buckets=(0.25, 1.0, 10.0),
                          observations=(0.2, 0.9, 1.0, 3.9)),
    ]
    for script in scripts:
        job = Job("127.0.0.1", punchcard.port, secret="s3cret", script=script)
        job.submit()
        st = job.wait(timeout=120)
        assert st["status"] == "finished", st["output"]

    agg = Job("127.0.0.1", punchcard.port, secret="s3cret").aggregate()
    assert agg["status"] == "ok"
    assert agg["jobs"] == 2
    assert agg["snapshot"]["jobs_trained_total"] == {"type": "counter",
                                                     "value": 5.0}
    golden = open(os.path.join(GOLDEN, "telemetry_aggregate.txt")).read()
    assert agg["prometheus"] == golden
    # the metrics verb carries the same fleet view alongside the daemon's
    # own registry
    fleet = Job("127.0.0.1", punchcard.port, secret="s3cret").metrics()["fleet"]
    assert fleet["snapshot"] == agg["snapshot"]

    # flush-on-job-finish: each job's telemetry landed in its own dir, and
    # the daemon counted + flushed its own registry per job
    tel_root = os.path.join(punchcard.workdir, "telemetry")
    per_job = [d for d in os.listdir(tel_root)
               if any(f.startswith("metrics_")
                      for f in os.listdir(os.path.join(tel_root, d)))]
    assert len(per_job) == 2
    assert telemetry.metrics.snapshot()[
        "punchcard_jobs_finished_total"]["value"] == 2.0


def test_daemon_flush_on_stop(tmp_path):
    # clean_telemetry points DISTKERAS_TELEMETRY_DIR at tmp_path; stop()
    # must write the daemon's trace/metrics there instead of waiting for
    # interpreter exit (daemons are typically killed, not exited)
    server = PunchcardServer(port=0, secret="x")
    server.start()
    telemetry.metrics.counter("punchcard_smoke_total").inc()
    server.stop()
    files = os.listdir(tmp_path)
    assert any(f.startswith("metrics_") for f in files)
    assert any(f.startswith("trace_") for f in files)


# ------------------------------------------------------------- ScalarLogger

def test_scalar_logger_context_manager_closes_on_error(tmp_path, monkeypatch):
    monkeypatch.setattr(ScalarLogger, "_try_torch", lambda self: False)
    with pytest.raises(RuntimeError):
        with ScalarLogger(str(tmp_path)) as log:
            log.log(0, loss=1.0)
            raise RuntimeError("boom")
    assert log._jsonl is None  # closed despite the exception
    rec = json.loads(open(tmp_path / "scalars.jsonl").read().splitlines()[0])
    assert rec == {"step": 0, "loss": 1.0}


def test_scalar_logger_tf_fallback_to_jsonl(tmp_path, monkeypatch):
    monkeypatch.setenv("DISTKERAS_TB_TF", "1")
    monkeypatch.setattr(ScalarLogger, "_try_torch", lambda self: False)
    monkeypatch.setattr(ScalarLogger, "_try_tf", lambda self: False)
    log = ScalarLogger(str(tmp_path))  # must not raise
    log.log(1, loss=0.5)
    log.close()
    assert (tmp_path / "scalars.jsonl").exists()


def test_scalar_logger_close_idempotent_when_never_wrote(tmp_path, monkeypatch):
    monkeypatch.setattr(ScalarLogger, "_try_torch", lambda self: False)
    log = ScalarLogger(str(tmp_path))
    log.close()
    log.close()  # idempotent
    assert not (tmp_path / "scalars.jsonl").exists()  # lazy open: no file


# ---------------------------------------------------------------- profiler

def test_profiler_hook_windowing(monkeypatch):
    calls = []
    monkeypatch.setattr(ProfilerHook, "_start", lambda self: calls.append("start"))
    monkeypatch.setattr(ProfilerHook, "_stop", lambda self: calls.append("stop"))
    hook = ProfilerHook("/tmp/prof", start_step=1, stop_step=3)
    for step in range(5):
        hook.on_step(step)
    hook.close()
    assert calls == ["start", "stop"]  # started at 1, stopped entering 3
    assert hook.done


def test_profiler_hook_close_stops_midwindow(monkeypatch):
    calls = []
    monkeypatch.setattr(ProfilerHook, "_start", lambda self: calls.append("start"))
    monkeypatch.setattr(ProfilerHook, "_stop", lambda self: calls.append("stop"))
    hook = ProfilerHook("/tmp/prof", start_step=0)
    hook.on_step(0)
    hook.close()
    assert calls == ["start", "stop"]


def test_profiler_from_env(monkeypatch, tmp_path):
    assert ProfilerHook.from_env() is None
    monkeypatch.setenv("DISTKERAS_PROFILE", str(tmp_path))
    monkeypatch.setenv("DISTKERAS_PROFILE_STEPS", "2:4")
    hook = ProfilerHook.from_env()
    assert (hook.logdir, hook.start_step, hook.stop_step) == (str(tmp_path), 2, 4)


# ------------------------------------------------------------- end to end

def _train(toy, num_epoch=2, **kwargs):
    x, y, onehot = toy
    t = dk.DOWNPOUR(FlaxModel(MLP(features=(16,), num_classes=2)),
                    loss="categorical_crossentropy",
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=4, batch_size=16, num_epoch=num_epoch,
                    communication_window=4, seed=7, **kwargs)
    t.train(from_numpy(x, onehot))
    return t


EPOCH_SPANS = ("epoch", "epoch_arrays", "h2d", "h2d_transfer", "dispatch",
               "device_epoch", "stats_wait")


def _ring_spans():
    """The ring's spans of the loop itself: a ``gc`` span (a collection that
    held the interpreter over a millisecond, whenever it fell:
    ``tests/test_serving_loop_spans.py``) is none of these tests'."""
    telemetry.trace.drain(5.0)
    return [s for s in telemetry.flightdeck.recorder.spans()
            if s["name"] != "gc"]


class _SyncLog:
    """Patches ``jax.block_until_ready`` and ``np.asarray`` to log
    ``(thread name, open span)`` of every call that waits for a device
    array."""

    def __init__(self, monkeypatch):
        import jax

        self.calls = []
        real_block, real_asarray = jax.block_until_ready, np.asarray

        def block(x):
            self.calls.append((threading.current_thread().name,
                               telemetry.trace.current()))
            return real_block(x)

        def asarray(a, *args, **kwargs):
            if isinstance(a, jax.Array):
                self.calls.append((threading.current_thread().name,
                                   telemetry.trace.current()))
            return real_asarray(a, *args, **kwargs)

        monkeypatch.setattr(jax, "block_until_ready", block)
        monkeypatch.setattr(np, "asarray", asarray)


@pytest.mark.parametrize("on", [False, True], ids=["off", "on"])
def test_trajectory_unchanged_by_telemetry(toy_classification, monkeypatch, on):
    """Telemetry on and off run the same program with the same inputs, and
    neither makes the training thread wait for the device between the gather
    and the dispatch: the only waits there belong to the readiness thread."""
    telemetry.configure(False)
    base = _train(toy_classification).get_history()["loss"]
    telemetry.configure(on)
    telemetry.trace.reset()
    telemetry.metrics.reset()
    telemetry.flightdeck.recorder.reset()
    log = _SyncLog(monkeypatch)
    me = threading.current_thread().name
    instrumented = _train(toy_classification).get_history()["loss"]
    assert instrumented == base  # bit-identical: same program, same inputs
    mine = [span for thread, span in log.calls if thread == me]
    # the training thread waits for the device where it reads an epoch's
    # losses (stats_wait) and when it builds the returned model (no span
    # open: after the loop), nowhere inside an epoch's gather, put or dispatch
    assert "stats_wait" in mine
    assert set(mine) <= {"stats_wait", None}, mine
    # the spans that end on the device were closed by the readiness thread,
    # which waits for nothing either (it asks ``is_ready``)
    assert {thread for thread, _ in log.calls} == {me}
    probed = [s["name"] for s in _ring_spans()
              if s["thread"] == "dk-telemetry-probe"]
    assert sorted(probed) == ["device_epoch"] * 2 + ["h2d_transfer"] * 2


def test_smoke_train_writes_nested_chrome_trace(toy_classification, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    _train(toy_classification)

    traces = [f for f in os.listdir(tmp_path) if f.startswith("trace_")]
    assert len(traces) == 1
    payload = json.load(open(tmp_path / traces[0]))  # must json.load cleanly
    events = payload["traceEvents"]
    parents = {}
    for e in events:
        parents.setdefault(e["name"], set()).add(e["args"].get("parent"))
    # the nesting: every epoch-grain span sits under its epoch (but the
    # wait for the last epoch's losses, after the loop); the in-memory path
    # has no window/step/commit any more (one fused program)
    for name in EPOCH_SPANS[1:]:
        assert parents[name] == ({"epoch", None} if name == "stats_wait"
                                 else {"epoch"}), name
    assert not {"window", "step", "commit"} & set(parents)
    epochs = [e for e in events if e["name"] == "epoch"]
    assert [e["args"]["epoch"] for e in epochs] == [0, 1]
    # containment in time, not just labels: the training thread's spans of
    # the first epoch sit inside the first epoch
    ep = epochs[0]
    for name in ("epoch_arrays", "h2d", "dispatch"):
        w = min((e for e in events if e["name"] == name), key=lambda e: e["ts"])
        assert w["args"]["epoch"] == 0
        assert ep["ts"] <= w["ts"] and w["ts"] + w["dur"] <= ep["ts"] + ep["dur"]
    # the clock anchor: the wall clock at the origin that ``ts`` counts from
    anchor = payload["otherData"]["clock_anchor"]
    assert anchor == dict(zip(("perf_counter_s", "time_ns"),
                              telemetry.trace.anchor))
    assert abs(anchor["time_ns"] - time.time_ns()) < 3600e9

    metrics_files = [f for f in os.listdir(tmp_path) if f.startswith("metrics_")]
    assert len(metrics_files) == 1
    snap = json.loads(open(tmp_path / metrics_files[0]).read().splitlines()[-1])
    bd = {k: v for k, v in snap["metrics"].items() if k.startswith("phase_")}
    # data, h2d (the transfer) and step (the device's epoch) saw time during
    # an in-memory train; commit has no boundary in one fused program
    assert {"phase_data_seconds", "phase_h2d_seconds",
            "phase_step_seconds"} <= set(bd)
    assert "phase_commit_seconds" not in bd
    assert telemetry.metrics.phase_breakdown()["commit"] == 0.0
    assert bd["phase_step_seconds"]["count"] == 2  # one device_epoch an epoch
    assert snap["metrics"]["training_seconds"]["value"] > 0
    assert snap["metrics"]["samples_per_sec_per_chip"]["value"] > 0


def test_fit_with_telemetry_off_leaves_epoch_spans_in_ring(toy_classification):
    """(a) The seven epoch-grain spans are in the ring for every epoch of a
    default run, with the epoch's id and absolute times that nest."""
    telemetry.configure(False)
    telemetry.flightdeck.recorder.reset()
    before = time.perf_counter()
    _train(toy_classification, num_epoch=3)
    after = time.perf_counter()
    spans = _ring_spans()
    assert telemetry.trace.export()["traceEvents"] == []  # the ring alone
    by = {}
    for s in spans:
        assert before <= s["t0"] <= s["t1"] <= after  # absolute perf_counter
        by.setdefault(s["attrs"].get("epoch"), {}).setdefault(
            s["name"], []).append(s)
    for epoch in range(3):
        names = set(by[epoch])
        # nothing to wait for in the first iteration: stats_wait from 1 on
        want = set(EPOCH_SPANS) - ({"stats_wait"} if epoch == 0 else set())
        assert names == want, (epoch, names)
        ep = by[epoch]["epoch"][0]
        assert ep["parent"] is None and ep["attrs"]["epochs"] == 1
        for name in ("epoch_arrays", "h2d", "dispatch", "stats_wait"):
            for s in by[epoch].get(name, ()):
                assert s["parent"] == "epoch" and s["thread"] == ep["thread"]
                assert ep["t0"] <= s["t0"] and s["t1"] <= ep["t1"]
        order = [by[epoch][n][0] for n in ("epoch_arrays", "h2d", "dispatch")]
        assert all(a["t1"] <= b["t0"] for a, b in zip(order, order[1:]))
        put, transfer = by[epoch]["h2d"][0], by[epoch]["h2d_transfer"][0]
        assert transfer["thread"] == "dk-telemetry-probe"
        assert transfer["t0"] <= put["t0"] and transfer["t1"] >= put["t0"]
        assert transfer["attrs"]["bytes"] == put["attrs"]["bytes"] > 0
        gather = by[epoch]["epoch_arrays"][0]["attrs"]
        assert gather["bytes"] == put["attrs"]["bytes"] and gather["rows"] == 512
        device = by[epoch]["device_epoch"][0]
        assert device["thread"] == "dk-telemetry-probe"
        assert device["t0"] >= by[epoch]["dispatch"][0]["t1"]
        assert by[epoch]["dispatch"][0]["attrs"]["windows"] == 2
    # the last epoch's losses are read after the loop, under no epoch
    assert list(by[None]) == ["stats_wait"]
    assert "epoch" in telemetry.flightdeck.recorder.last_spans()  # /healthz


def test_probe_drops_reference_and_never_waits(monkeypatch):
    """(c) The readiness thread lets go of what it watched at once; a full
    queue drops the probe, counts it, and does not wait."""
    import gc
    import weakref

    # ``telemetry.trace`` is the tracer; its module is under the same name
    trace_mod = sys.modules["distkeras_tpu.telemetry.trace"]

    class Rows:
        def __init__(self, gate):
            self.gate = gate

        def is_ready(self):
            return self.gate.is_set()

        def __eq__(self, other):  # as a jax array against a tuple of them
            raise TypeError("unsupported operand type(s) for ==")

        __hash__ = None

    monkeypatch.setattr(trace_mod, "PROBE_DEPTH", 2)
    tr = Tracer()
    gate = threading.Event()
    rows = Rows(gate)
    ref = weakref.ref(rows)
    t0 = time.perf_counter()
    assert tr.probe(rows, "h2d_transfer", t0, bytes=8)
    del rows
    assert tr.probe(Rows(gate), "h2d_transfer", t0)  # two are unfinished
    t1 = time.perf_counter()
    assert tr.probe(Rows(gate), "h2d_transfer", t0) is False  # dropped
    assert time.perf_counter() - t1 < 0.5  # and did not wait for room
    assert tr.probes_lost == 1
    assert not tr.drain(0.05)  # two are still held
    gate.set()
    assert tr.drain(10.0)
    gc.collect()
    assert ref() is None  # nothing keeps the rows alive
    events = tr.export()["traceEvents"]
    assert [e["name"] for e in events] == ["h2d_transfer"] * 2
    assert sorted(map(str, (e["args"] for e in events))) == [
        "{'bytes': 8}", "{}"]
    # every span ends when its own arrays are ready, whatever came before it
    late, soon = threading.Event(), threading.Event()
    assert tr.probe(Rows(late), "device_epoch", t0)
    assert tr.probe(Rows(soon), "h2d_transfer", t0, bytes=9)
    soon.set()
    deadline = time.perf_counter() + 5.0
    while len(tr.export()["traceEvents"]) < 3 and time.perf_counter() < deadline:
        time.sleep(0.001)
    assert [e["args"] for e in tr.export()["traceEvents"]
            if e["args"].get("bytes") == 9]  # not behind the epoch before it
    assert tr.probes_lost == 1  # and settling it out of turn lost nothing
    late.set()
    assert tr.drain(10.0)

    # arrays that raise (the device's own error) do not kill the thread
    class Broken:
        def is_ready(self):
            raise RuntimeError("device error")

    assert tr.probe(Broken(), "device_epoch", t0)
    assert tr.probe(Rows(gate), "device_epoch", t0)
    assert tr.drain(10.0)
    assert [e["name"] for e in tr.export()["traceEvents"]].count("device_epoch") == 2
    assert tr.probes_lost == 2  # the full queue's and this one


@pytest.mark.parametrize("dues, now, want", [
    ((), 5.0, None),                # nothing held: sleep until a probe comes
    ((5.5,), 5.0, 0.02),            # its end is far: one idle poll
    ((5.5, 5.008), 5.0, 0.008),     # no further than the first span's due
    ((5.0004,), 5.0, 0.001),        # about to be due: one fine poll
    ((4.0, 9.0), 5.0, 0.001),       # one is past its due: fine polls
])
def test_readiness_thread_sleeps_until_a_span_is_due(dues, now, want):
    """The wake-ups are the always-on cost: idle polls while every held
    span has most of its expected length before it, fine polls after."""
    trace_mod = sys.modules["distkeras_tpu.telemetry.trace"]
    got = trace_mod._poll_wait(list(dues), now)
    assert got == (want if want is None else pytest.approx(want))


def test_readiness_thread_expects_a_span_to_last_like_the_last_two():
    """A first span of a name is looked at every fine poll from its start;
    later ones once nine tenths of the shorter of the last two have passed,
    and still end where their arrays were ready."""

    class Rows:
        looks = 0

        def __init__(self, gate):
            self.gate = gate

        def is_ready(self):
            self.looks += 1
            return self.gate.is_set()

    tr = Tracer()
    probe = tr._probe
    assert probe._due((None, "device_epoch", 7.0)) == 7.0  # no history
    took = []
    probe._took["h2d_transfer"] = (0.19, 5.0)  # a short one: no idle poll
    assert probe._due((None, "h2d_transfer", 7.0)) == 7.0
    for length in (0.3, 0.3, 0.3):
        gate = threading.Event()
        rows = Rows(gate)
        t0 = time.perf_counter()
        assert tr.probe(rows, "device_epoch", t0)
        threading.Timer(length, gate.set).start()
        assert tr.drain(10.0)
        took.append((rows.looks, tr.export()["traceEvents"][-1]["dur"] / 1e6))
    for looks, dur in took:
        assert 0.3 <= dur < 2.0  # never before the arrays were ready
    a, b = probe._took["device_epoch"]
    assert probe._due((None, "device_epoch", 7.0)) == pytest.approx(
        7.0 + 0.9 * min(a, b))
    # 0.27 s of idle polls and 0.03 s of fine ones, against 0.3 s of fine
    assert took[1][0] < took[0][0] and took[2][0] < took[0][0]
    assert tr.probes_lost == 0


def test_num_updates_advances_during_chunked_fit(toy_classification):
    """(d) ``_train_chunked`` tracks the commit counter after each chunk's
    dispatch, so ``num_updates`` moves while a ``dispatch_epochs=2`` fit
    runs (the benchmark's clock)."""
    from distkeras_tpu.parameter_servers import ParameterServer

    seen = []
    real = ParameterServer.track

    def track(self, center_rule_state):
        real(self, center_rule_state)
        seen.append(self.num_updates)

    x, y, onehot = toy_classification
    t = dk.DOWNPOUR(FlaxModel(MLP(features=(16,), num_classes=2)),
                    loss="categorical_crossentropy",
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=4, batch_size=16, num_epoch=4,
                    communication_window=4, seed=7, dispatch_epochs=2)
    ParameterServer.track = track
    try:
        t.train(from_numpy(x, onehot))
    finally:
        ParameterServer.track = real
    # 2 windows x 4 workers an epoch, 2 epochs a chunk: 16 commits a chunk
    assert seen == [16, 32]
    assert t.num_updates == 32
    spans = _ring_spans()
    chunks = [s["attrs"] for s in spans if s["name"] == "epoch"][-2:]
    assert chunks == [{"epoch": 0, "epochs": 2}, {"epoch": 2, "epochs": 2}]


@pytest.mark.parametrize("kwargs,steps", [
    ({}, [0, 1, 2]), ({"dispatch_epochs": 2}, [0, 1])],
    ids=["per_epoch", "chunked"])
def test_profile_dir_goes_through_profiler_hook(toy_classification, tmp_path,
                                                monkeypatch, kwargs, steps):
    """(e) ``profile_dir=`` is one profiler path: a ``ProfilerHook`` over
    the loop's second iteration, started and stopped exactly once."""
    import jax

    calls = []
    monkeypatch.setattr(ProfilerHook, "_start",
                        lambda self: calls.append(("start", self.logdir)))
    monkeypatch.setattr(ProfilerHook, "_stop",
                        lambda self: calls.append(("stop", self.logdir)))
    real_on_step = ProfilerHook.on_step
    seen = []

    def on_step(self, step):
        seen.append(step)
        real_on_step(self, step)

    monkeypatch.setattr(ProfilerHook, "on_step", on_step)
    monkeypatch.setattr(jax.profiler, "trace", None)  # the old inline path
    monkeypatch.setattr(jax.profiler, "start_trace", None)
    _train(toy_classification, num_epoch=3 if not kwargs else 4,
           profile_dir=str(tmp_path / "prof"), **kwargs)
    assert seen == steps
    assert calls == [("start", str(tmp_path / "prof")),
                     ("stop", str(tmp_path / "prof"))]


def test_streaming_train_records_spans(toy_classification):
    _train(toy_classification, num_epoch=1, streaming=True)
    names = {e["name"] for e in telemetry.trace.export()["traceEvents"]}
    # streaming records its real sync points, per window (the switch governs
    # those), and none of the in-memory path's epoch-grain spans but "epoch"
    assert {"epoch", "window_dispatch", "window_h2d", "window_gather"} <= names
    # "h2d" is the in-memory path's name; a window's put is an enqueue
    assert not {"dispatch", "device_epoch", "h2d", "h2d_transfer",
                "epoch_arrays"} & names
    assert telemetry.metrics.phase_breakdown()["h2d"] == 0.0
