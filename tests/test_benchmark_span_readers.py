"""The benchmark's readers of the program's epoch-grain spans
(``benchmark/readers/spans.py``) on a hand-made ring whose answers are worked
out by hand."""

import json

import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.telemetry.flightdeck import FlightRecorder

# One job of 5 epochs, seconds on the program's clock.  Per epoch:
# (gather, put's enqueue, transfer, dispatch, device_epoch, stats_wait), each
# (start, end).  Epoch 0 compiles and epoch 5 is another fit's: both ignored.
TIMELINE = {
    0: ((99.0, 99.9), (99.9, 99.91), (99.9, 99.95), (99.95, 100.0),
        (100.0, 101.0), None),
    1: ((100.1, 100.5), (100.5, 100.51), (100.5, 100.8), (100.51, 100.512),
        (100.512, 102.0), (100.52, 101.0)),
    2: ((101.0, 101.6), (101.6, 101.61), (101.6, 102.1), (101.61, 101.614),
        (101.614, 103.1), (101.62, 102.0)),
    3: ((102.02, 102.5), (102.5, 102.51), (102.5, 102.9), (102.51, 102.516),
        (102.516, 103.15), (102.52, 103.1)),
    4: ((103.2, 103.5), (103.5, 103.51), (103.5, 103.7), (103.7, 103.75),
        (103.75, 105.0), (103.76, 103.761)),
    5: ((200.0, 209.0), (209.0, 209.1), (209.0, 218.0), (209.1, 218.1),
        (218.1, 227.0), (218.2, 227.0)),
}
NAMES = ("epoch_arrays", "h2d", "h2d_transfer", "dispatch", "device_epoch",
         "stats_wait")
PROBED = ("h2d_transfer", "device_epoch")

# By hand, over the epochs 1..4:
#   gather    400, 600, 480, 300 ms                  -> median 440
#   transfer  300, 500, 400, 200 ms                  -> median 350
#   dispatch    2,   4,   6,  50 ms                  -> median 5
#   wait      480, 380, 580,   1 ms                  -> median 430
#   gap(2) = max(102.1, 101.614) - 102.0  = 100 ms, all under the transfer
#            of epoch 2 (the gather of epoch 3 begins in it at 102.02 and
#            does not count: a later iteration's)
#   gap(3) = max(102.9, 102.516) - 103.1  < 0 -> 0
#   gap(4) = max(103.7, 103.75) - 103.15  = 600 ms: [103.15, 103.2] bare
#            (50), gather 300, put 10, transfer alone [103.51, 103.7] 190,
#            dispatch 50
#   gaps 100, 0, 600 -> median 100; bare 50 of 700 ms -> 7.142857 %
ANSWERS = {"gather_ms": 440.0, "h2d_ms": 350.0, "dispatch_ms": 5.0,
           "host_slack_ms": 430.0, "feed_gap_ms": 100.0,
           "gap_unattributed_share": 100.0 * 50.0 / 700.0}


def _ring(timeline):
    ring = FlightRecorder()
    for epoch, spans in timeline.items():
        for name, span in zip(NAMES, spans):
            if span is not None:
                thread = "dk-telemetry-probe" if name in PROBED else "MainThread"
                ring.record_timed_span(name, span[0], span[1], thread, "epoch",
                                       {"epoch": epoch})
    # the wait for the last epoch's losses, under no epoch: never counted
    ring.record_timed_span("stats_wait", 300.0, 309.0, "MainThread", None, {})
    return ring


FACTS = {"job": {"epochs": 5}, "traced_epoch": 3,
         "trace": {"between": {"seconds": 0.59}}}


@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_reader_on_hand_made_ring(harness, monkeypatch, capsys, name):
    monkeypatch.setattr(telemetry.flightdeck, "recorder", _ring(TIMELINE))
    reader = harness.resolve("readers", harness.metric_spec(name)["reader"])
    assert reader(dict(FACTS)) == pytest.approx(ANSWERS[name], rel=1e-9)
    out = capsys.readouterr().out
    if name != "feed_gap_ms":
        assert out == ""
        return
    note = json.loads(out)["feed_gap"]
    assert sorted(note["epochs"]) == ["2", "3", "4"]
    assert note["epochs"]["3"] == {"gap_ms": 0.0, "split_ms": {}}
    split = note["epochs"]["4"]["split_ms"]
    assert split == pytest.approx({"epoch_arrays": 300.0, "h2d": 10.0,
                                   "dispatch": 50.0, "h2d_transfer": 190.0,
                                   "unattributed": 50.0})
    assert note["epochs"]["2"]["split_ms"] == pytest.approx(
        {"h2d_transfer": 100.0})
    # the capture held the end of epoch 3: the gap before epoch 4, beside
    # the device trace's own reading of that boundary
    assert note["traced_gap_epoch"] == 4
    assert note["traced_gap_ms"] == pytest.approx(600.0)
    assert note["trace_between_ms"] == pytest.approx(590.0)
    # the program's count of probes whose span it never recorded: an epoch
    # short of one is left out of the medians, and the line says so
    assert note["probes_lost"] == telemetry.trace.probes_lost


@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_reader_finds_nothing_on_an_empty_ring(harness, monkeypatch, capsys, name):
    reader = harness.resolve("readers", harness.metric_spec(name)["reader"])
    monkeypatch.setattr(telemetry.flightdeck, "recorder", FlightRecorder())
    assert reader(dict(FACTS)) is None
    # a program from before the ring kept timed spans (the parent): no such
    # method, nothing read and nothing raised
    monkeypatch.setattr(telemetry.flightdeck, "recorder", object())
    assert reader(dict(FACTS)) is None
    assert capsys.readouterr().out == ""


def test_unattributed_share_needs_a_millisecond_of_gap(harness, monkeypatch):
    """A device that never waits (the GPT-2 cell) has no gap to attribute:
    the gap reads 0 and its share is left out."""
    fed = {e: spans[:4] + ((spans[4][0], spans[4][0] + 9.0),) + spans[5:]
           for e, spans in TIMELINE.items()}
    monkeypatch.setattr(telemetry.flightdeck, "recorder", _ring(fed))
    read = lambda name: harness.resolve("readers", f"spans:{name}")(dict(FACTS))
    assert read("feed_gap_ms") == 0.0
    assert read("gap_unattributed_share") is None
    assert read("gather_ms") == pytest.approx(440.0)


def test_traced_run_line_carries_the_span_metrics(harness, monkeypatch, capsys):
    """Through ``harness.result_line`` as ``run.py`` calls it: every new
    metric under its name and unit in a cell it applies to, and
    ``gap_unattributed_share`` in the CNN cell alone."""
    monkeypatch.setattr(telemetry.flightdeck, "recorder", _ring(TIMELINE))
    manifest = harness.load_manifest()
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] in ANSWERS]
    run = {"correct": True, "attempted": 5, "failed": 0, "facts": dict(FACTS),
           "end_to_end": {}, "device": {}}
    for cell, names in (
            ("cifar_cnn.downpour_1chip", set(ANSWERS)),
            ("gpt2_small.downpour_1chip",
             set(ANSWERS) - {"gap_unattributed_share"})):
        line = harness.result_line(manifest, {"name": cell}, run, True)
        assert set(line["metrics"]) == names
        assert line["metrics"]["feed_gap_ms"] == {
            "value": pytest.approx(100.0), "unit": "ms"}
    capsys.readouterr()
