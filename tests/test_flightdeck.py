"""Flight-deck tests: the bounded flight-recorder ring (wrap order, overhead
pin, disabled-path silence), the live HTTP exporter (/metrics /healthz /vars
/trace answered mid-fit under concurrent scrapes), run_id correlation
(minting, env inheritance, span stamping, labelled Prometheus golden),
blackbox crash dumps (unit + a real watchdog halt through a trainer), and
the daemon's live job scrape through the punchcard ``status``/``metrics``
verbs."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import telemetry
from distkeras_tpu.frame import from_numpy
from distkeras_tpu.job_deployment import Job, PunchcardServer
from distkeras_tpu.models import MLP, FlaxModel
from distkeras_tpu.telemetry.dynamics import TrainingDiverged
from distkeras_tpu.telemetry.flightdeck import correlate
from distkeras_tpu.telemetry.flightdeck import server as server_mod
from distkeras_tpu.telemetry.flightdeck.recorder import (
    FlightRecorder,
    blackbox_dump,
    recorder,
)
from distkeras_tpu.telemetry.metrics import Registry, prometheus_from_snapshot
from distkeras_tpu.telemetry.trace import Tracer

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")


@pytest.fixture(autouse=True)
def clean_flightdeck(tmp_path, monkeypatch):
    """Each test runs enabled, correlated under a fixed run_id, with empty
    tracer/registry/ring, and leaves every global env-driven again."""
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setattr(telemetry.dynamics, "_LAST_SUMMARY", None)
    telemetry.configure(True)
    telemetry.trace.reset()
    telemetry.metrics.reset()
    recorder.reset()
    correlate.set_run_id("testrun")
    yield
    server_mod.stop()
    server_mod.configure(None)
    telemetry.trace.reset()
    telemetry.metrics.reset()
    recorder.reset()
    correlate.set_run_id(None)
    telemetry.dynamics.configure()
    telemetry.configure(None)


def _get(addr, path, timeout=10):
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=timeout) as r:
        return r.status, r.read().decode("utf-8")


# -------------------------------------------------------------------- ring

def test_ring_wraps_and_keeps_newest_oldest_first():
    ring = FlightRecorder(capacity=8)
    for i in range(20):
        ring.record_metric(f"m{i}", float(i))
    evs = ring.events()
    assert [e["name"] for e in evs] == [f"m{i}" for i in range(12, 20)]
    assert all(e["kind"] == "metric" for e in evs)
    # timestamps are monotone oldest-first across the wrap seam
    perfs = [e["perf"] for e in evs]
    assert perfs == sorted(perfs)


def test_ring_partial_fill_and_reset():
    ring = FlightRecorder(capacity=8)
    ring.record_span({"name": "epoch", "ph": "X", "ts": 0.0, "dur": 1.0,
                      "args": {}})
    ring.record_watchdog({"action": "warn", "epoch": 3})
    evs = ring.events()
    assert [e["kind"] for e in evs] == ["span", "watchdog"]
    assert evs[0]["event"]["name"] == "epoch"
    assert ring.last_spans() == {"epoch": evs[0]["unix"]}
    assert ring.watchdog_state() == {"action": "warn", "epoch": 3}
    assert ring.last_event_unix() == evs[-1]["unix"]
    ring.reset()
    assert ring.events() == []
    assert ring.last_event_unix() is None
    assert ring.watchdog_state() is None


def test_ring_record_overhead_pin():
    """Recording is a tuple build + a list store under one lock: it must stay
    within a small constant factor of a bare dict store.  Generous bound +
    absolute floor to stay unflaky on loaded CI machines."""
    ring = FlightRecorder(capacity=1024)
    n = 20000
    d = {}
    t0 = time.perf_counter()
    for i in range(n):
        d["k"] = i
    dict_t = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        ring.record_metric("m", 1.0)
    ring_t = time.perf_counter() - t0
    assert ring_t < max(150 * dict_t, 0.05), (
        f"ring record cost {ring_t:.4f}s vs dict store {dict_t:.4f}s"
    )


def test_disabled_telemetry_feeds_nothing_into_the_ring():
    telemetry.configure(False)
    recorder.reset()
    telemetry.metrics.counter("c").inc()
    with telemetry.trace.span("epoch"):
        pass  # NOOP span: never reaches the tracer, never reaches the ring
    assert recorder.events() == []


def test_trace_export_places_instants_on_span_axis():
    ring = FlightRecorder(capacity=8)
    ring.record_span({"name": "epoch", "ph": "X", "ts": 100.0, "dur": 5.0,
                      "pid": 1, "tid": 1, "args": {}})
    ring.record_metric("commits_total", 2.0)
    payload = ring.trace_export()
    assert payload["displayTimeUnit"] == "ms"
    spans = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    instants = [e for e in payload["traceEvents"] if e.get("ph") == "i"]
    assert spans[0]["ts"] == 100.0  # original event passes through untouched
    assert instants[0]["name"] == "metric:commits_total"
    assert instants[0]["args"] == {"value": 2.0}
    assert instants[0]["ts"] >= 0.0


# ------------------------------------------------------------- correlation

def test_run_id_minting_env_inheritance_and_force(monkeypatch):
    correlate.set_run_id(None)
    monkeypatch.delenv("DISTKERAS_RUN_ID", raising=False)
    assert correlate.current() is None  # never mints
    rid = correlate.run_id()
    assert len(rid) == 12 and correlate.current() == rid
    assert correlate.run_id() == rid  # stable once minted

    correlate.set_run_id(None)
    monkeypatch.setenv("DISTKERAS_RUN_ID", "inherited01")
    assert correlate.current() == "inherited01"
    assert correlate.run_id() == "inherited01"  # env wins over minting


def test_correlated_tracer_stamps_run_id_and_feeds_ring():
    with telemetry.trace.span("epoch", epoch=0):
        pass
    ev = telemetry.trace.export()["traceEvents"][0]
    assert ev["args"]["epoch"] == 0
    assert ev["args"]["run_id"] == "testrun"
    ring = recorder.events()
    assert [e["kind"] for e in ring] == ["span"]
    assert ring[0]["event"]["args"]["run_id"] == "testrun"


def test_injected_tracer_stays_pure():
    # test-constructed tracers must not stamp run_ids or feed the global
    # ring — the Chrome-trace golden depends on exact args
    tr = Tracer(pid=0)
    with tr.span("epoch", epoch=0):
        pass
    assert tr.export()["traceEvents"][0]["args"] == {"epoch": 0}
    assert recorder.events() == []


def test_flush_carries_run_id(tmp_path):
    telemetry.metrics.counter("c").inc()
    _, metrics_path = telemetry.flush()
    line = json.loads(open(metrics_path).read().splitlines()[-1])
    assert line["run_id"] == "testrun"


def test_prometheus_run_id_label_golden():
    reg = Registry()
    reg.counter("jax_compiles_total", help="compile events").inc(3)
    reg.gauge("samples_per_sec_per_chip").set(1234.5)
    h = reg.histogram("phase_step_seconds", help="step phase",
                      buckets=(0.001, 0.01, 0.1))
    h.observe(0.0005)
    h.observe(0.05)
    golden = open(os.path.join(GOLDEN, "flightdeck_metrics.txt")).read()
    assert reg.to_prometheus(labels={"run_id": "fleet1234"}) == golden
    # and the unlabeled rendering is untouched by the label plumbing
    assert 'run_id' not in reg.to_prometheus()


def test_prometheus_from_snapshot_carries_labels():
    snap = {"dynamics_grad_norm": {"type": "gauge", "value": 2.5, "mean": 2.0}}
    text = prometheus_from_snapshot(snap, labels={"run_id": "r"})
    assert 'dynamics_grad_norm{agg="max",run_id="r"} 2.5' in text
    assert 'dynamics_grad_norm{agg="mean",run_id="r"} 2' in text


# ---------------------------------------------------------------- exporter

def test_http_port_gate(monkeypatch):
    for raw, want in (("", None), ("off", None), ("false", None),
                      ("no", None), ("0", 0), ("9123", 9123)):
        server_mod.configure(None)  # re-read the env
        if raw:
            monkeypatch.setenv("DISTKERAS_TELEMETRY_HTTP", raw)
        else:
            monkeypatch.delenv("DISTKERAS_TELEMETRY_HTTP", raising=False)
        assert server_mod.http_port() == want, raw


def test_exporter_off_by_default_and_when_disabled():
    server_mod.configure(None)
    assert telemetry.flightdeck.ensure_server() is None  # no port configured
    server_mod.configure(0)
    telemetry.configure(False)
    assert telemetry.flightdeck.ensure_server() is None  # telemetry off
    assert telemetry.flightdeck.address() is None


def test_exporter_endpoints_and_discovery_file(tmp_path):
    server_mod.configure(0)
    rid = telemetry.flightdeck.activate()
    assert rid == "testrun"
    addr = telemetry.flightdeck.address()
    assert addr is not None and addr.startswith("127.0.0.1:")
    assert telemetry.flightdeck.ensure_server() == addr  # idempotent

    telemetry.metrics.counter("commits_total").inc(3)
    with telemetry.trace.span("epoch", epoch=0):
        pass

    code, text = _get(addr, "/metrics")
    assert code == 200
    assert 'commits_total{run_id="testrun"} 3' in text

    code, text = _get(addr, "/healthz")
    health = json.loads(text)
    assert (code, health["status"], health["run_id"]) == (200, "ok", "testrun")
    assert health["pid"] == os.getpid()
    assert "epoch" in health["last_spans"]
    assert health["last_event_unix"] is not None
    assert health["uptime_seconds"] >= 0
    assert health["sanitizer"]["mode"] in ("off", "warn", "strict")
    assert isinstance(health["sanitizer"]["violations"], dict)
    assert health["probes_lost"] == telemetry.trace.probes_lost

    code, text = _get(addr, "/vars")
    v = json.loads(text)
    assert (code, v["run_id"]) == (200, "testrun")
    assert v["metrics"]["commits_total"]["value"] == 3.0
    assert set(v["phase_breakdown"]) == {"data", "h2d", "step", "commit"}

    code, text = _get(addr, "/trace")
    tr = json.loads(text)
    epochs = [e for e in tr["traceEvents"] if e.get("name") == "epoch"]
    assert code == 200 and epochs[0]["args"]["run_id"] == "testrun"

    with pytest.raises(urllib.error.HTTPError) as err:
        _get(addr, "/nope")
    assert err.value.code == 404
    assert "/metrics" in err.value.read().decode()

    disc = json.loads(open(tmp_path / f"flightdeck_{os.getpid()}.json").read())
    assert disc == {"address": addr, "pid": os.getpid(), "run_id": "testrun"}

    server_mod.stop()
    assert telemetry.flightdeck.address() is None


def test_custom_endpoint_registry():
    server_mod.configure(0)
    addr = telemetry.flightdeck.ensure_server()
    telemetry.flightdeck.add_endpoint(
        "/aggregate", lambda: ("application/json", json.dumps({"jobs": 0})))
    code, text = _get(addr, "/aggregate")
    assert (code, json.loads(text)) == (200, {"jobs": 0})


# ------------------------------------------------------------ blackbox dump

def test_blackbox_dump_contents(tmp_path):
    telemetry.dynamics.record(
        2, {"grad_norm": np.ones(3, np.float32)}, {"grad_norm": 1.5})
    telemetry.metrics.counter("commits_total").inc(4)
    with telemetry.trace.span("epoch", epoch=2):
        pass
    path = blackbox_dump("unit test", extra={"job_id": "j1"})
    assert os.path.basename(path) == f"blackbox_testrun_{os.getpid()}.json"
    assert os.path.dirname(path) == str(tmp_path)
    bb = json.load(open(path))
    assert (bb["reason"], bb["run_id"], bb["pid"]) == (
        "unit test", "testrun", os.getpid())
    assert bb["dynamics"]["epoch"] == 2
    assert bb["dynamics"]["summary"]["grad_norm"] == 1.5
    assert bb["metrics"]["commits_total"]["value"] == 4.0
    assert bb["config"]["DISTKERAS_TELEMETRY_DIR"] == str(tmp_path)
    assert bb["extra"] == {"job_id": "j1"}
    kinds = [e["kind"] for e in bb["ring"]]
    assert "span" in kinds and "metric" in kinds
    spans = [e for e in bb["ring"] if e["kind"] == "span"]
    assert spans[-1]["event"]["args"]["run_id"] == "testrun"
    # the dump itself is counted, so fleet views can see crashes happened
    snap = telemetry.metrics.snapshot()
    assert snap["telemetry_blackbox_dumps_total"]["value"] == 1.0


def test_blackbox_dump_disabled_returns_none(tmp_path):
    telemetry.configure(False)
    assert blackbox_dump("nope") is None
    assert not [f for f in os.listdir(tmp_path) if f.startswith("blackbox_")]


def _mlp():
    return FlaxModel(MLP(features=(16,), num_classes=2))


def _toy(n=256, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d,))
    y = (x @ w > 0).astype(np.int32)
    onehot = np.zeros((n, 2), np.float32)
    onehot[np.arange(n), y] = 1.0
    return x, onehot


def test_watchdog_halt_dumps_blackbox(tmp_path):
    """Acceptance: a seeded watchdog halt leaves a blackbox file carrying the
    ring, the run_id, and the last dynamics summary."""
    telemetry.dynamics.configure(enabled=True, watchdog="halt")
    x, onehot = _toy()
    t = dk.DOWNPOUR(_mlp(), loss="categorical_crossentropy",
                    worker_optimizer=("sgd", {"learning_rate": 1e38}),
                    num_workers=2, batch_size=16, num_epoch=4,
                    communication_window=2, seed=7)
    with pytest.raises(TrainingDiverged):
        t.train(from_numpy(x, onehot))

    boxes = [f for f in os.listdir(tmp_path) if f.startswith("blackbox_")]
    assert boxes == [f"blackbox_testrun_{os.getpid()}.json"]
    bb = json.load(open(tmp_path / boxes[0]))
    assert bb["run_id"] == "testrun"
    assert "TrainingDiverged" in bb["reason"]
    assert bb["dynamics"] is not None  # the poisoned epoch's summary
    assert bb["watchdog"]["action"] == "halt"
    kinds = {e["kind"] for e in bb["ring"]}
    assert "watchdog" in kinds and "span" in kinds


# ------------------------------------------------------------- mid-fit scrape

def _train(toy, num_epoch=3):
    x, y, onehot = toy
    t = dk.DOWNPOUR(FlaxModel(MLP(features=(16,), num_classes=2)),
                    loss="categorical_crossentropy",
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=4, batch_size=16, num_epoch=num_epoch,
                    communication_window=4, seed=7)
    t.train(from_numpy(x, onehot))
    return t


def test_exporter_answers_mid_fit_under_concurrent_scrapes(toy_classification):
    """Acceptance: with the exporter on an ephemeral port, 4 scrape threads
    hammer every endpoint while a trainer fits, and each endpoint answered
    200 before fit returned."""
    server_mod.configure(0)
    addr = telemetry.flightdeck.activate() and telemetry.flightdeck.address()
    paths = ["/metrics", "/healthz", "/vars", "/trace"]
    results = []
    stop = threading.Event()

    def hammer(offset):
        while not stop.is_set():
            path = paths[(offset + len(results)) % len(paths)]
            try:
                code, _body = _get(addr, path, timeout=5)
            except urllib.error.URLError:
                code = -1
            results.append((path, code, time.monotonic()))

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    try:
        _train(toy_classification, num_epoch=3)
        t_fit_done = time.monotonic()
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=10)

    for path in paths:
        codes = [c for p, c, ts in results if p == path and ts < t_fit_done]
        assert 200 in codes, f"{path} never answered before fit returned"


# --------------------------------------------------------- daemon live jobs

_LIVE_JOB = """\
import json
import os
import time
import urllib.request

from distkeras_tpu import telemetry

telemetry.metrics.counter("job_steps_total").inc(7)
with telemetry.trace.span("job_work", step=0):
    pass
addr = telemetry.flightdeck.activate() and telemetry.flightdeck.address()
# prove the inherited gate + run_id: scrape our own exporter from inside
with urllib.request.urlopen(f"http://{addr}/vars", timeout=5) as r:
    assert json.loads(r.read())["run_id"] == os.environ["DISTKERAS_RUN_ID"]
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    if os.path.exists(r"{sentinel}"):
        break
    time.sleep(0.05)
telemetry.flush()
"""


def test_daemon_scrapes_live_job_and_status_carries_flightdeck(tmp_path,
                                                               monkeypatch):
    """Acceptance: a daemon with flightdeck on hands its jobs the ephemeral
    gate + run_id; ``status`` exposes the job's telemetry dir, live address,
    and heartbeat, and ``Job.metrics(job_id)`` scrapes the running job's
    /vars before the job exits."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("PYTHONPATH", repo)
    server_mod.configure(0)
    sentinel = tmp_path / "done"
    server = PunchcardServer(port=0, secret="s3cret")
    server.start()
    try:
        job = Job("127.0.0.1", server.port, secret="s3cret",
                  script=_LIVE_JOB.replace("{sentinel}", str(sentinel)))
        job.submit()

        deadline = time.monotonic() + 120
        st = {}
        while time.monotonic() < deadline:
            st = job.status()
            if st.get("http") or st.get("status") in ("finished", "failed"):
                break
            time.sleep(0.1)
        assert st.get("status") == "running", st
        assert st["http"], st
        assert st["telemetry_dir"] and os.path.isdir(st["telemetry_dir"])
        assert st["last_heartbeat"] is not None

        reply = Job("127.0.0.1", server.port, secret="s3cret").metrics(
            job_id=job.job_id)
        live = reply["live"]
        assert live is not None, reply
        assert live["metrics"]["job_steps_total"]["value"] == 7.0
        assert live["run_id"] == "testrun"  # daemon's run_id, inherited

        sentinel.write_text("go")
        st = job.wait(timeout=120)
        assert st["status"] == "finished", st.get("output")
        # both the daemon's and the job's traces carry the same fleet run_id
        tel_dir = st["telemetry_dir"]
        trace_files = [f for f in os.listdir(tel_dir)
                       if f.startswith("trace_")]
        payload = json.load(open(os.path.join(tel_dir, trace_files[0])))
        rids = {e["args"].get("run_id") for e in payload["traceEvents"]}
        assert rids == {"testrun"}
    finally:
        server.stop()
