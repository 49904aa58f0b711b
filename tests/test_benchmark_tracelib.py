"""The benchmark's reducer (``benchmark/tracelib.py``): from a profiler trace
to the numbers behind the ledger's ``device_idle_share``, ``flash_time_share``,
``flash_roofline``, ``dispatches_per_epoch`` and every ``breakdown``.  On
intervals and planes made by hand, and on the hand-made two-chip trace of
``benchmark/testdata/`` (``selftest.py::check_trace`` works its numbers out in
its docstring).  All times are nanoseconds."""

import os
from types import SimpleNamespace

import pytest

from conftest import BENCH

TRACE = os.path.join(BENCH, "testdata", "small_trace.textproto")
NS = 1e-9


@pytest.fixture
def tracelib(harness):
    import tracelib

    return tracelib


def plane(name, **lines):
    """A plane as ``jax.profiler.ProfileData`` gives it, from
    ``line=[(start, end, name), ...]``; ``XLA_Ops`` names the line ``XLA Ops``."""
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=line.replace("_", " "), events=[
            SimpleNamespace(start_ns=s, duration_ns=e - s, name=n)
            for s, e, n in events])
        for line, events in lines.items()])


# --------------------------------------------------------------- intervals

# name -> (intervals, lo, hi, nanoseconds covered, the gaps)
INTERVALS = {
    "disjoint": ([(0, 10), (20, 30)], 0, 40, 20, [(10, 20), (30, 40)]),
    "overlapping": ([(5, 20), (0, 10)], 0, 20, 20, []),
    "nested": ([(0, 100, "while"), (10, 20, "a"), (30, 40, "b")], 0, 120, 100,
               [(100, 120)]),
    # (0, 10) and (5, 20) are cut to 2..20, (30, 40) to 30..38
    "clipped_at_lo_and_hi": ([(0, 10), (5, 20), (30, 40), (35, 36)], 2, 38, 26,
                             [(20, 30)]),
    "wholly_outside": ([(0, 5), (50, 60)], 10, 40, 0, [(10, 40)]),
    "empty": ([], 5, 9, 0, [(5, 9)]),
}


@pytest.mark.parametrize("case", sorted(INTERVALS))
def test_union_ns(tracelib, case):
    intervals, lo, hi, covered, _ = INTERVALS[case]
    assert tracelib.union_ns(intervals, lo, hi) == covered


@pytest.mark.parametrize("case", sorted(INTERVALS))
def test_gaps_of(tracelib, case):
    intervals, lo, hi, covered, gaps = INTERVALS[case]
    assert tracelib.gaps_of(intervals, lo, hi) == gaps
    # what is not covered is a gap
    assert sum(e - s for s, e in gaps) == (hi - lo) - covered


# -------------------------------------------------------------- self times

SELF_TIMES = {
    # a while whose children cover it is charged nothing
    "children_cover_the_while": (
        [(0, 100, "while"), (0, 60, "a"), (60, 100, "b")], 0, 100,
        {"while": 0, "a": 60, "b": 40}),
    # the slice ends at 50: the while is 50 long there, b 10 of its 40
    "a_child_outlives_the_slice": (
        [(0, 100, "while"), (10, 30, "a"), (40, 80, "b")], 0, 50,
        {"while": 20, "a": 20, "b": 10}),
    "siblings_of_one_name_add_up": (
        [(0, 10, "a"), (10, 25, "b"), (30, 40, "a")], 0, 40,
        {"a": 20, "b": 15}),
    "two_levels": (
        [(0, 100, "while"), (10, 90, "call"), (20, 50, "f")], 0, 100,
        {"while": 20, "call": 50, "f": 30}),
    "nothing_in_the_slice": ([(0, 10, "a")], 20, 30, {}),
}


@pytest.mark.parametrize("case", sorted(SELF_TIMES))
def test_self_times(tracelib, case):
    ops, lo, hi, want = SELF_TIMES[case]
    assert tracelib.self_times(ops, lo, hi) == want


def test_events_of_puts_a_parent_before_its_children(tracelib):
    """One line's events by start, the longer first; two lines of one name
    are one line; another line is not read."""
    device = plane("/device:TPU:0", XLA_Ops=[(5, 9, "b"), (0, 4, "a")])
    device.lines += plane("", XLA_Ops=[(0, 10, "while")],
                          XLA_Modules=[(0, 10, "jit_f(1)")]).lines
    assert tracelib.events_of(device, "XLA Ops") == [
        (0, 10, "while"), (0, 4, "a"), (5, 9, "b")]
    assert tracelib.events_of(device, "Steps") == []


# ------------------------------------------------------------ epoch module


def test_epoch_module_is_the_program_with_most_device_time(tracelib):
    # five copies of 1 ns and one short epoch of 2 x 20 against one of 45
    modules = ([(i, i + 1, "jit_copy(2)") for i in range(5)]
               + [(10, 30, "jit_epoch_fn(1)"), (30, 75, "jit_eval(3)"),
                  (80, 100, "jit_epoch_fn(1)")])
    assert tracelib.epoch_module(modules) == "jit_eval(3)"
    assert tracelib.epoch_module(modules[:-2]) == "jit_epoch_fn(1)"


def test_epoch_module_of_no_modules_is_none(tracelib):
    assert tracelib.epoch_module([]) is None


# --------------------------------------------------------------- HLO texts

FUSION = ("%fusion.1 = bf16[256,64]{1,0:T(8,128)(2,1)} fusion(bf16[256,64]"
          "{1,0:T(8,128)(2,1)} %p.0), kind=kOutput, calls=%fused_computation.1")
PALLAS = ("%_SelfAttention_0.5 = (bf16[96,1024,64]{2,1,0:T(8,128)(2,1)}, "
          "f32[96,1,1024]{2,1,0:T(1,128)}) custom-call(bf16[96,1024,64]"
          "{2,1,0:T(8,128)(2,1)} %bitcast.1, bf16[96,1024,64]"
          "{2,1,0:T(8,128)(2,1)} %bitcast.2)")
START = ("%all-reduce-start.1 = f32[64]{0:T(128)} all-reduce-start(f32[64]"
         "{0:T(128)} %fusion.2), replica_groups={{0,1}}, to_apply=%add")
DONE = ("%all-reduce-done.1 = f32[64]{0:T(128)} all-reduce-done(f32[64]"
        "{0:T(128)} %all-reduce-start.1)")
WHILE = ("%while.1 = (s32[]{:T(128)}, f32[64]{0:T(128)}) while((s32[]"
         "{:T(128)}, f32[64]{0:T(128)}) %tuple.3), condition=%cond.1, "
         "body=%body.1")
# text -> (op_name, op_kind, generic_name)
HLO = {
    "a_fusion": (FUSION, "fusion.1", "fusion", "fusion"),
    "a_fusion_named_after_its_root": (
        "%multiply_add_fusion.123 = f32[8]{0} fusion(f32[8]{0} %p.0), "
        "kind=kLoop, calls=%fused_computation.9",
        "multiply_add_fusion.123", "fusion", "multiply_add_fusion"),
    # a Pallas call: a custom-call named after the flax module that made it,
    # its result a tuple
    "a_pallas_custom_call": (PALLAS, "_SelfAttention_0.5", "custom-call",
                             "_SelfAttention_"),
    "a_collective_start": (START, "all-reduce-start.1", "all-reduce-start",
                           "all-reduce-start"),
    "a_collective_done": (DONE, "all-reduce-done.1", "all-reduce-done",
                          "all-reduce-done"),
    "a_while_over_a_tuple": (WHILE, "while.1", "while", "while"),
    # no HLO text (a hand-made trace, another profiler): its own kind
    "a_bare_name": ("all-gather-start.3", "all-gather-start.3",
                    "all-gather-start", "all-gather-start"),
    "a_host_event": ("DevicePutWithSharding", "DevicePutWithSharding",
                     "DevicePutWithSharding", "DevicePutWithSharding"),
    "digits_alone": ("123", "123", "123", "123"),
}


@pytest.mark.parametrize("case", sorted(HLO))
def test_hlo_text_to_name_kind_and_generic_name(tracelib, case):
    text, name, kind, generic = HLO[case]
    assert tracelib.op_name(text) == name
    assert tracelib.op_kind(text) == kind
    assert tracelib.generic_name(text) == generic


def test_the_kinds_select_collectives_and_parents(tracelib):
    kinds = {text: tracelib.op_kind(text)
             for text in (FUSION, PALLAS, START, DONE, WHILE)}
    assert [bool(tracelib.COLLECTIVE.match(kinds[t]))
            for t in (FUSION, PALLAS, START, DONE, WHILE)] == [
                False, False, True, True, False]
    assert [bool(tracelib.PARENT.match(kinds[t]))
            for t in (FUSION, PALLAS, START, DONE, WHILE)] == [
                False, False, False, False, True]


# -------------------------------------------------------------- gap labels

# name -> (host events, gaps, labels)
LABELS = {
    "under_one_host_event": ([(5, 25, "put")], [(10, 20)], ["put"]),
    # both cover all of it: the shorter says more
    "under_two_that_cover_it_equally": (
        [(5, 25, "long"), (10, 20, "short")], [(10, 20)], ["short"]),
    # (0, 14) covers 4 of it, (12, 30) covers 8
    "under_two_the_one_that_covers_most": (
        [(0, 14, "a"), (12, 30, "b")], [(10, 20)], ["b"]),
    "under_none": ([(30, 40, "x")], [(10, 20)], ["unattributed"]),
    "no_host_events": ([], [(10, 20), (30, 40)],
                       ["unattributed", "unattributed"]),
    "one_event_over_two_gaps": (
        [(5, 25, "e"), (42, 43, "f")], [(0, 10), (20, 30), (35, 38), (40, 50)],
        ["e", "e", "unattributed", "f"]),
    # an event that only touches a gap's edge covers none of it
    "touching_is_not_covering": ([(0, 10, "before"), (20, 30, "after")],
                                 [(10, 20)], ["unattributed"]),
}


@pytest.mark.parametrize("case", sorted(LABELS))
def test_label_gaps(tracelib, case):
    host, gaps, labels = LABELS[case]
    assert tracelib.label_gaps(host, gaps) == labels


def test_only_the_longest_gaps_are_matched_with_the_host(tracelib):
    """103 operations of 5 ns leave 102 gaps: 100 of 5 ns and two of 1 ns.
    The host event covers them all; the 100 longest are labelled with it (500
    ns) and the two shortest are summed under a name of their own (2 ns)."""
    assert tracelib.LABELLED == 100
    ops, at = [], 0
    for index in range(103):
        ops.append((at, at + 5, "fusion.1"))
        at += 5 + (1 if index in (40, 41) else 5)
    end = ops[-1][1]
    got = tracelib.reduce_planes([
        plane("/device:TPU:0", XLA_Modules=[(0, end, "jit_epoch_fn(1)")],
              XLA_Ops=ops),
        plane("/host:CPU", python=[(0, end, "host_wait")])])
    assert got["epochs"] == 0 and got["between"] is None
    assert got["window_s"] == pytest.approx(end * NS)
    assert got["busy_s"] == pytest.approx(103 * 5 * NS)
    gaps = [[name, round(s / NS, 6)] for name, s in got["breakdown"]["idle_gaps"]]
    assert gaps == [["host_wait", 500],
                    ["gaps shorter than the 100 longest", 2]]


# ----------------------------------------------------------- reduce_planes


def test_reduce_the_two_chip_trace(tracelib):
    """``testdata/small_trace.textproto``: the slice runs from the first end
    of the epoch program to the last end that another program follows, 400 to
    2440; the execution from 2460, which nothing follows, is left out."""
    got = tracelib.reduce_file(TRACE)
    assert got["chips"] == 2 and got["epochs"] == 2
    assert got["epoch_module"] == "jit_epoch_fn(1234)"
    assert got["between"] is None
    assert got["window_s"] == pytest.approx(2040 * NS)
    # 2 copies and 2 epochs begin in the slice; the copy at 2445 does not
    assert got["modules_per_epoch"] == pytest.approx(2.0)
    assert [c["plane"] for c in got["per_chip"]] == [
        "/device:TPU:0", "/device:TPU:1"]
    assert [round(c["busy_s"] / NS) for c in got["per_chip"]] == [2002, 1982]
    assert got["busy_s"] == pytest.approx(1992 * NS)
    assert all("named_s" not in c for c in got["per_chip"])


def test_collectives_are_summed_over_the_slice(tracelib):
    """An all-reduce-start of 10 ns and its -done of 100 ns in each of the
    two epochs, on both chips: 220 ns; the while that encloses them adds
    nothing, and is no row of the breakdown."""
    got = tracelib.reduce_file(TRACE)
    assert got["collective_s"] == pytest.approx(220 * NS)
    named = {k: round(v / NS, 6) for k, v in got["named_s"].items()}
    assert named == {"fusion fusion.1": 1230, "fusion fusion.2": 140,
                     "custom-call _SelfAttention_0.5": 400,
                     "all-reduce-start all-reduce-start.1": 20,
                     "all-reduce-done all-reduce-done.1": 200,
                     "copy copy.9": 2}
    ops = [[n, round(v / NS, 6)] for n, v in got["breakdown"]["device_ops"]]
    assert ops == [["fusion", 1370], ["_SelfAttention_", 400],
                   ["all-reduce-done", 200], ["all-reduce-start", 20],
                   ["copy", 2]]


def test_the_gaps_of_the_two_chip_trace_are_labelled_by_the_host(tracelib):
    """Means over the chips: 29 ns under DevicePutWithSharding (the shorter
    PjitFunction covers less), 10 under Wait for donation holds, 9 under no
    host event."""
    got = tracelib.reduce_file(TRACE)
    gaps = [[n, round(v / NS, 6)] for n, v in got["breakdown"]["idle_gaps"]]
    assert gaps == [["DevicePutWithSharding", 29],
                    ["Wait for donation holds", 10], ["unattributed", 9]]
    assert sum(v for _, v in gaps) == 2040 - 1992


def test_a_trace_without_an_op_line_is_reduced_by_its_modules(tracelib):
    """Epoch program 0..100, 110..210, 220..320, the counter's copy behind
    each of the first two.  Nothing follows the third execution, so the
    slice is [100, 210): one epoch, in which the copy at 105 and the epoch at
    110 begin; busy 1 + 100 of 110 ns."""
    got = tracelib.reduce_planes([plane("/device:TPU:0", XLA_Modules=[
        (0, 100, "jit_epoch_fn(1)"), (105, 106, "jit_copy(2)"),
        (110, 210, "jit_epoch_fn(1)"), (215, 216, "jit_copy(2)"),
        (220, 320, "jit_epoch_fn(1)")])])
    assert got["chips"] == 1 and got["epochs"] == 1
    assert got["window_s"] == pytest.approx(110 * NS)
    assert got["busy_s"] == pytest.approx(101 * NS)
    assert got["modules_per_epoch"] == pytest.approx(2.0)
    assert got["collective_s"] == 0.0


BOUNDARY = dict(
    XLA_Modules=[(0, 100, "jit_epoch_fn(1)"), (110, 112, "jit_copy(2)"),
                 (150, 200, "jit_epoch_fn(1)")],
    XLA_Ops=[(10, 60, "fusion.1"), (70, 100, "all-reduce.2"),
             (110, 112, "copy.9"), (150, 195, "fusion.1")])


def test_a_boundary_capture_gives_between(tracelib):
    """A capture too short for two ends, holding one end (100) and the next
    execution's beginning (150): measured whole, 10..195, busy 50 + 30 + 2 +
    45; between the two the device idles 48 of 50 ns and 2 programs begin."""
    got = tracelib.reduce_planes([plane("/device:TPU:0", **BOUNDARY)])
    assert got["epochs"] == 0
    assert got["window_s"] == pytest.approx(185 * NS)
    assert got["busy_s"] == pytest.approx(127 * NS)
    assert got["collective_s"] == pytest.approx(30 * NS)
    assert got["between"] == pytest.approx(
        {"seconds": 50 * NS, "idle_s": 48 * NS, "modules": 2})
    assert got["modules_per_epoch"] == 2


def test_a_capture_inside_one_epoch_gives_neither(tracelib):
    """The next epoch's program never begins: ``epochs`` 0, no ``between``,
    no modules an epoch."""
    inside = {line: events[:-1] for line, events in BOUNDARY.items()}
    got = tracelib.reduce_planes([plane("/device:TPU:0", **inside)])
    assert got["epochs"] == 0 and got["between"] is None
    assert got["modules_per_epoch"] is None
    assert got["window_s"] == pytest.approx(102 * NS)
    assert got["busy_s"] == pytest.approx(82 * NS)


def test_a_trace_without_a_device_plane_reduces_to_none(tracelib):
    """A capture on the CPU: host threads only."""
    host = plane("/host:CPU", python=[(0, 10, "PjitFunction(f)")])
    assert tracelib.reduce_planes([host]) is None
    assert tracelib.reduce_planes([plane("/device:TPU:0")]) is None
    assert tracelib.reduce_planes([]) is None


# ------------------------------------------------------------------- files


def test_find_xplane_takes_the_newest_run_of_the_profile_layout(tracelib, tmp_path):
    for run in ("2026_01_02_00_00_00", "2026_01_01_00_00_00"):
        folder = tmp_path / "plugins" / "profile" / run
        folder.mkdir(parents=True)
        (folder / "host.xplane.pb").write_bytes(b"")
        (folder / "host.trace.json.gz").write_bytes(b"")
    (tmp_path / "stray.xplane.pb").write_bytes(b"")
    assert tracelib.find_xplane(str(tmp_path)) == str(
        tmp_path / "plugins" / "profile" / "2026_01_02_00_00_00"
        / "host.xplane.pb")


def test_find_xplane_on_an_empty_directory_is_none(tracelib, tmp_path):
    assert tracelib.find_xplane(str(tmp_path)) is None
    assert tracelib.find_xplane(str(tmp_path / "never_made")) is None


def test_load_reads_a_textproto(tracelib):
    planes = list(tracelib.load(TRACE).planes)
    assert [p.name for p in planes] == [
        "/device:TPU:0", "/device:TPU:1", "/host:CPU"]
    modules = tracelib.events_of(planes[1], "XLA Modules")
    assert modules[2] == (420, 1400, "jit_epoch_fn(1234)")
    assert len(tracelib.events_of(planes[0], "XLA Ops")) == 19


def test_load_refuses_garbage(tracelib, tmp_path):
    """Bytes that are no XSpace and a file that is not there raise; text that
    stops being a proto is read as far as it went, and reduces to nothing."""
    binary = tmp_path / "garbage.xplane.pb"
    binary.write_bytes(b"\xff\xfe no protobuf \x00\x01")
    with pytest.raises(RuntimeError):
        tracelib.load(str(binary))
    with pytest.raises(Exception, match="absent.xplane.pb"):
        tracelib.load(str(tmp_path / "absent.xplane.pb"))
    text = tmp_path / "garbage.textproto"
    text.write_text("planes { this is no proto")
    assert tracelib.reduce_file(str(text)) is None
