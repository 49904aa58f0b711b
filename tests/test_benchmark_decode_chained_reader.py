"""The benchmark's reader of the serving loop's chained-step counter
(``benchmark/readers/decode_chained.py``) on hand-made ``facts["marks"]``,
found the way a run finds it: by the metric's file, and through the traced
run's last line as ``run.py`` makes it."""

import json

import pytest

CHAINED = "serving_decode_steps_chained_total"
STEPS = "serving_decode_steps_total"
NAME = "decode_chained_share"


@pytest.fixture
def reader(harness):
    return harness.resolve("readers", harness.metric_spec(NAME)["reader"])


def _edge(chained, steps, **others):
    return {CHAINED: chained, STEPS: steps,
            "serving_token_latency_seconds": (1.0, 10), **others}


# By hand: 820 decode steps inside the window; 812 of them were dispatched
# while the step before was unread (the loop went idle eight times).
CASES = {
    "both_edges": (
        {"open": _edge(95.0, 100.0), "close": _edge(907.0, 920.0)},
        100.0 * 812.0 / 820.0),
    "every_step_chained": (
        {"open": _edge(10.0, 11.0), "close": _edge(50.0, 51.0)}, 100.0),
    # counted for the first time inside the window: the loop was serial (or
    # idle) until then, and the opening edge has no such name
    "counter_missing_at_the_opening_edge": (
        {"open": {STEPS: 100.0}, "close": _edge(410.0, 920.0)}, 50.0),
    # no step was chained at all, though the program counts them
    "a_loop_that_never_got_ahead": (
        {"open": _edge(0.0, 100.0), "close": _edge(0.0, 300.0)}, 0.0),
    "one_edge_missing": ({"close": _edge(812.0, 820.0)}, None),
    "no_marks": (None, None),
    # the parent of the PR that brought the counter: it counts its steps and
    # has nothing to say about chaining: not 0 %, nothing
    "a_program_without_the_counter": (
        {"open": {STEPS: 100.0, "serving_tokens_total": 5.0},
         "close": {STEPS: 920.0, "serving_tokens_total": 9.0}}, None),
    "no_step_inside_the_window": (
        {"open": _edge(7.0, 9.0), "close": _edge(7.0, 9.0)}, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_chained_share_on_hand_made_marks(reader, case):
    marks, want = CASES[case]
    got = reader({"marks": marks})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("case", ["both_edges",
                                  "a_program_without_the_counter"])
def test_the_line_is_made_with_and_without_the_counter(harness, case):
    """Through ``harness.result_line`` in the metric's own cell: on this
    program's marks the line carries the share; on the parent's (the driver
    lays this reader over a checkout that has no such counter) the metric is
    left out and the line is made all the same."""
    marks, want = CASES[case]
    manifest = harness.load_manifest()
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_tokens_per_s",
        # every serving cell reports it: a later one is appended behind
        "workloads": ["gpt2_small.serve_prefill_heavy",
                      "sarvam_105b.serve_closed_decode"]
        + entry["workloads"][2:]}
    run = {"correct": True, "attempted": 5, "failed": 0,
           "facts": {"marks": marks}, "end_to_end": {},
           "device": {"platform": "tpu"}}
    line = harness.result_line(dict(manifest, per_layer=[entry]),
                               {"name": entry["workloads"][0]}, run, True)
    json.dumps(line)
    if want is None:
        assert line["metrics"] == {}
    else:
        assert line["metrics"] == {NAME: {
            "value": pytest.approx(want, rel=1e-12), "unit": "%"}}
    assert line["correct"] is True and line["attempted"] == 5


def test_the_counter_the_reader_reads_is_the_engines():
    """The name in the reader is the name on the engine's registry."""
    from distkeras_tpu.serving import serving_metrics
    from distkeras_tpu.telemetry.metrics import Registry

    registry = Registry()
    serving_metrics(registry)
    assert {CHAINED, STEPS} <= set(registry.snapshot())
