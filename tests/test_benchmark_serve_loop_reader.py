"""The benchmark's readers of the serving loop's own account of its time
(``benchmark/readers/serve_loop.py``) on hand-made ``facts["marks"]`` and a
hand-made ring, found the way a run finds them: by each metric's file, and
through the traced run's last line as ``run.py`` makes it."""

import json

import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.telemetry.flightdeck import FlightRecorder

ITERATION = "serving_loop_iteration_seconds"
DISPATCH = "serving_loop_dispatch_seconds"
WAIT = "serving_loop_wait_seconds"
EMIT = "serving_loop_emit_seconds"
IDLE = "serving_loop_idle_seconds"
QUEUE_WAIT = "serving_queue_wait_seconds"
DISPATCHES = "serving_dispatches_total"
STARVED = "serving_dispatches_starved_total"
GC_PAUSE = "serving_gc_pause_seconds_total"
SEVEN = ("loop_host_ms", "loop_wait_share", "step_dispatch_ms", "emit_ms",
         "queue_wait_ms", "dispatch_starved_share", "gc_pause_share")
CELLS = ["gpt2_small.serve_prefill_heavy", "sarvam_105b.serve_closed_decode",
         "longcat_flash_omni.serve_closed_reasoning"]

# A window of 20 s (100.0 to 120.0 on the program's clock).  Between its
# edges, by hand:
#   2,000 iterations took 19.4 s, of which the loop waited 13.4 s for the
#   device in 3,000 reads (2,000 steps and 1,000 prefills): its own work is
#   6.0 s, 3.0 ms an iteration, and it had 67% of the window to spare;
#   10 idle passes took 0.5 s: 19.9 of 20 s are accounted for (99.5%);
#   the 2,000 step dispatches took 2.4 s (1.2 ms each), the 3,000 emits
#   1.5 s (0.5 ms each); 1,000 requests stood 1,900 s in the queue (1.9 s
#   each); 12 of 3,000 dispatches found the device empty (0.4%); collections
#   took 0.05 s (0.25% of the window).
OPEN = {ITERATION: (5.0, 500), DISPATCH: (0.6, 500), WAIT: (3.0, 700),
        EMIT: (0.4, 700), IDLE: (0.3, 6), QUEUE_WAIT: (400.0, 200),
        DISPATCHES: 700.0, STARVED: 3.0, GC_PAUSE: 0.01,
        "serving_token_latency_seconds": (4.0, 500)}
CLOSE = {ITERATION: (24.4, 2500), DISPATCH: (3.0, 2500), WAIT: (16.4, 3700),
         EMIT: (1.9, 3700), IDLE: (0.8, 16), QUEUE_WAIT: (2300.0, 1200),
         DISPATCHES: 3700.0, STARVED: 15.0, GC_PAUSE: 0.06,
         "serving_token_latency_seconds": (22.0, 2500)}
ANSWERS = {"loop_host_ms": 3.0, "loop_wait_share": 67.0,
           "step_dispatch_ms": 1.2, "emit_ms": 0.5, "queue_wait_ms": 1900.0,
           "dispatch_starved_share": 0.4, "gc_pause_share": 0.25}
FACTS = {"window": (100.0, 120.0), "marks": {"open": OPEN, "close": CLOSE}}
# the parent of the PR that brought the account: its engine has the older
# instruments and none of these
PARENT = {"window": (100.0, 120.0), "marks": {
    "open": {"serving_token_latency_seconds": (4.0, 500),
             "serving_decode_steps_total": 500.0},
    "close": {"serving_token_latency_seconds": (22.0, 2500),
              "serving_decode_steps_total": 2500.0}}}


def _reader(harness, name):
    return harness.resolve("readers", harness.metric_spec(name)["reader"])


@pytest.fixture
def empty_ring(monkeypatch):
    ring = FlightRecorder()
    monkeypatch.setattr(telemetry.flightdeck, "recorder", ring)
    return ring


@pytest.mark.parametrize("name", SEVEN)
def test_reader_on_marks_worked_out_by_hand(harness, empty_ring, name):
    assert _reader(harness, name)(dict(FACTS)) == pytest.approx(
        ANSWERS[name], rel=1e-9)


@pytest.mark.parametrize("name", SEVEN)
def test_reader_finds_nothing_on_a_program_without_the_account(
        harness, empty_ring, capsys, name):
    reader = _reader(harness, name)
    assert reader(dict(PARENT)) is None
    assert reader({"window": (100.0, 120.0), "marks": None}) is None
    assert reader({"window": (100.0, 120.0),
                   "marks": {"close": CLOSE}}) is None
    assert capsys.readouterr().out == ""  # and no note line either


@pytest.mark.parametrize("name", SEVEN)
def test_an_instrument_missing_at_the_opening_edge_stood_at_nought(
        harness, empty_ring, name):
    """Every instrument first touched inside the window (an engine that had
    not run an iteration before it opened): the closing edge is all of it."""
    facts = {"window": (100.0, 120.0), "marks": {"open": {}, "close": CLOSE}}
    want = {"loop_host_ms": 1e3 * (24.4 - 16.4) / 2500,
            "loop_wait_share": 100.0 * 16.4 / 20.0,
            "step_dispatch_ms": 1e3 * 3.0 / 2500,
            "emit_ms": 1e3 * 1.9 / 3700,
            "queue_wait_ms": 1e3 * 2300.0 / 1200,
            "dispatch_starved_share": 100.0 * 15.0 / 3700.0,
            "gc_pause_share": 100.0 * 0.06 / 20.0}
    assert _reader(harness, name)(facts) == pytest.approx(want[name], rel=1e-9)


def test_nothing_gained_inside_the_window_reads_nothing(harness, empty_ring):
    """An engine that sat idle through the window: the shares of the window
    read 0, the means over no observation read nothing."""
    facts = {"window": (100.0, 120.0), "marks": {"open": CLOSE, "close": CLOSE}}
    for name in ("loop_host_ms", "step_dispatch_ms", "emit_ms",
                 "queue_wait_ms", "dispatch_starved_share"):
        assert _reader(harness, name)(dict(facts)) is None
    for name in ("loop_wait_share", "gc_pause_share"):
        assert _reader(harness, name)(dict(facts)) == 0.0


def _ring():
    """Three iterations still in the ring, seconds on the program's clock:
    iteration 7 took 100 ms with a collection of 60 ms inside its emit,
    iteration 8 took 12 ms, iteration 9 took 30 ms; an idle pass, and a
    training loop's span that is none of the reader's."""
    ring = FlightRecorder()
    put = lambda name, t0, t1, parent, **attrs: ring.record_timed_span(
        name, t0, t1, "serving-engine", parent, attrs)
    put("serving.loop.admit", 118.000, 118.001, "serving.loop", iter=7,
        admitted=0)
    put("serving.loop.dispatch", 118.001, 118.003, "serving.loop", iter=7,
        seq=40, active=24, uploaded=False, level=0)
    put("serving.loop.wait", 118.003, 118.010, "serving.loop", iter=7,
        seq=39, kind="step")
    ring.record_timed_span("gc", 118.020, 118.080, "bench-client-3",
                           None, {"generation": 2, "collected": 31})
    put("serving.loop.emit", 118.010, 118.100, "serving.loop", iter=7,
        seq=39, rows=24, finished=1)
    put("serving.loop", 118.000, 118.100, None, iter=7, admitted=0, active=24,
        starved=0)
    put("serving.loop.prefill", 118.100, 118.103, "serving.loop.admit",
        iter=8, seq=41, slot=2, width=512, plen=400)
    put("serving.loop.admit", 118.100, 118.104, "serving.loop", iter=8,
        admitted=1)
    put("serving.loop.dispatch", 118.104, 118.106, "serving.loop", iter=8,
        seq=42, active=24, uploaded=True, level=0)
    put("serving.loop.wait", 118.106, 118.111, "serving.loop", iter=8,
        seq=40, kind="step")
    put("serving.loop.emit", 118.111, 118.112, "serving.loop", iter=8,
        seq=40, rows=24, finished=0)
    put("serving.loop", 118.100, 118.112, None, iter=8, admitted=1, active=24,
        starved=1)
    put("serving.loop", 118.112, 118.142, None, iter=9, admitted=0, active=24,
        starved=0)
    put("serving.loop.idle", 118.142, 118.192, None, iter=10)
    ring.record_timed_span("epoch", 1.0, 2.0, "MainThread", None, {"epoch": 7})
    return ring


def test_the_note_line_carries_coverage_and_the_longest_iterations(
        harness, monkeypatch, capsys):
    monkeypatch.setattr(telemetry.flightdeck, "recorder", _ring())
    assert _reader(harness, "loop_host_ms")(dict(FACTS)) == pytest.approx(3.0)
    note = json.loads(capsys.readouterr().out)["serve_loop"]
    assert note["window_s"] == pytest.approx(20.0)
    assert note["iteration_s"] == pytest.approx(19.4)
    assert note["idle_s"] == pytest.approx(0.5)
    assert note["coverage_share"] == pytest.approx(99.5)
    assert note["wait_s"] == pytest.approx(13.4)
    assert (note["iterations"], note["idle_passes"], note["waits"]) == (
        2000, 10, 3000)
    assert note["programs"] == pytest.approx(3000.0)
    assert note["starved"] == pytest.approx(12.0)
    assert note["gc_pause_s"] == pytest.approx(0.05)
    assert note["serving_loop_spans_in_ring"] == 3
    assert note["gc_spans_in_ring"] == 1
    assert note["collections_in_process"] == telemetry.trace.gc_collections
    longest = note["longest"]
    assert [row["iter"] for row in longest] == [7, 9, 8]
    assert longest[0]["ms"] == pytest.approx(100.0)
    assert longest[0]["phases_ms"] == pytest.approx(
        {"admit": 1.0, "prefill": 0.0, "dispatch": 2.0, "wait": 7.0,
         "emit": 90.0})
    assert longest[0]["gc_ms"] == pytest.approx([60.0])  # another thread's
    assert longest[2]["phases_ms"] == pytest.approx(
        {"admit": 4.0, "prefill": 3.0, "dispatch": 2.0, "wait": 5.0,
         "emit": 1.0})
    assert (longest[2]["admitted"], longest[2]["starved"]) == (1, 1)
    assert longest[1]["gc_ms"] == [] and longest[2]["gc_ms"] == []
    # the other six write no line
    for name in SEVEN[1:]:
        _reader(harness, name)(dict(FACTS))
    assert capsys.readouterr().out == ""


def test_the_note_is_made_on_a_program_whose_ring_keeps_no_times(
        harness, monkeypatch, capsys):
    monkeypatch.setattr(telemetry.flightdeck, "recorder", object())
    assert _reader(harness, "loop_host_ms")(dict(FACTS)) == pytest.approx(3.0)
    note = json.loads(capsys.readouterr().out)["serve_loop"]
    assert note["coverage_share"] == pytest.approx(99.5)
    assert "longest" not in note


@pytest.mark.parametrize("facts", ["change", "parent"])
def test_the_line_is_made_with_and_without_the_account(
        harness, empty_ring, capsys, facts):
    """Through ``harness.result_line`` in each of the three serving cells:
    on this program's marks the line carries all seven; on the parent's (the
    driver lays these readers over a checkout whose loop keeps no account)
    they are left out and the line is made all the same."""
    manifest = harness.load_manifest()
    entries = [m for m in manifest["per_layer"] if m["name"] in SEVEN]
    assert [m["name"] for m in entries] == list(SEVEN)
    assert [m["name"] for m in manifest["per_layer"][-7:]] == list(SEVEN)
    for entry in entries:
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["workloads"][:3] == CELLS
        assert entry["layer"] == ("scheduler" if entry["name"] in (
            "emit_ms", "queue_wait_ms") else "serving engine")
        assert entry["source"] == ("program_counter" if entry["name"] in (
            "dispatch_starved_share", "gc_pause_share") else "program_span")
        assert entry["better"] == ("higher" if entry["name"] ==
                                   "loop_wait_share" else "lower")
        assert entry["unit"] == ("%" if entry["name"].endswith("share")
                                 else "ms")
    run = {"correct": True, "attempted": 5, "failed": 0, "end_to_end": {},
           "facts": dict(FACTS if facts == "change" else PARENT),
           "device": {"platform": "tpu"}}
    for cell in CELLS:
        line = harness.result_line(dict(manifest, per_layer=entries),
                                   {"name": cell}, run, True)
        json.dumps(line)
        if facts == "parent":
            assert line["metrics"] == {}
        else:
            assert line["metrics"] == {
                name: {"value": pytest.approx(ANSWERS[name], rel=1e-9),
                       "unit": "%" if name.endswith("share") else "ms"}
                for name in SEVEN}
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == (3 if facts == "change" else 0)
    # a training cell is none of theirs
    line = harness.result_line(dict(manifest, per_layer=entries),
                               {"name": "cifar_cnn.downpour_1chip"}, run, True)
    assert line["metrics"] == {}


def test_the_instruments_the_readers_read_are_the_engines():
    """The names in the reader are the names on the engine's registry."""
    from distkeras_tpu.serving import serving_metrics
    from distkeras_tpu.telemetry.metrics import Registry

    registry = Registry()
    serving_metrics(registry)
    snap = registry.snapshot()
    assert {ITERATION, DISPATCH, WAIT, EMIT, IDLE, QUEUE_WAIT, DISPATCHES,
            STARVED, GC_PAUSE} <= set(snap)
    assert all(snap[name]["type"] == "histogram" for name in (
        ITERATION, DISPATCH, WAIT, EMIT, IDLE, QUEUE_WAIT))
    assert all(snap[name]["type"] == "counter" for name in (
        DISPATCHES, STARVED, GC_PAUSE))
