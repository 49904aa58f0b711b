"""The benchmark's reader of the decode step's two counters
(``benchmark/readers/decode_kv.py``) on hand-made ``facts["marks"]``, found
the way a run finds it: by the metric's file."""

import pytest

READ = "serving_decode_kv_positions_read_total"
CAPACITY = "serving_decode_kv_positions_capacity_total"


@pytest.fixture
def reader(harness):
    return harness.resolve(
        "readers", harness.metric_spec("decode_kv_read_share")["reader"])


def _edge(read, capacity, **others):
    return {READ: read, CAPACITY: capacity,
            "serving_token_latency_seconds": (1.0, 10), **others}


# By hand: 10 steps of 24 slots x 1,024 inside the window are 245,760
# positions of capacity; the steps were told to cover 190,464 of them.
CASES = {
    "both_edges": (
        {"open": _edge(1000.0, 24576.0), "close": _edge(191464.0, 270336.0)},
        100.0 * 190464.0 / 245760.0),
    # first touched inside the window: the opening edge has no such names
    "counters_missing_at_the_opening_edge": (
        {"open": {"serving_token_latency_seconds": (0.0, 0)},
         "close": _edge(190464.0, 245760.0)}, 77.5),
    "one_edge_missing": ({"close": _edge(190464.0, 245760.0)}, None),
    "no_marks": (None, None),
    # the parent of the PR that brought the counters: marks without them
    "a_program_without_the_counters": (
        {"open": {"serving_tokens_total": 5.0},
         "close": {"serving_tokens_total": 9.0}}, None),
    "no_step_inside_the_window": (
        {"open": _edge(7.0, 9.0), "close": _edge(7.0, 9.0)}, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_kv_read_share_on_hand_made_marks(reader, case):
    marks, want = CASES[case]
    got = reader({"marks": marks})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)
