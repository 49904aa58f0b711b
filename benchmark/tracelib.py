"""From a profiler trace to numbers: the benchmark's own reducer, on
``jax.profiler.ProfileData`` (nothing but JAX).

    python benchmark/tracelib.py <file.xplane.pb | file.textproto>   # look by hand

What a TPU trace of this libtpu (0.0.34) holds, looked at by hand in PR 23: one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one event
per executed program (``jit_epoch_fn(<hash>)``) and whose line ``XLA Ops`` has
one event per executed HLO operation, named by the operation's whole HLO text
(``%fusion.12 = f32[...] fusion(...)``), parents (``%while``) enclosing their
children; asynchronous copies appear as ``-start``/``-done`` pairs there and
as spans on ``Async XLA Ops``.  A Pallas call is a ``custom-call`` named after
the flax module that made it (``%_SelfAttention_0.279``).  Host threads are
lines of the plane ``/host:CPU``.  All times are nanoseconds on one clock.  A
program that was running when the capture began or ended is there cut short.

The slice that is measured runs from the end of the first to the end of the
last execution of the epoch program (the module with the most device time in
the trace) that another program follows: a whole number of epochs, each with
the gap before it.  The counter's copy follows every epoch that ended inside
the capture, so an execution that the capture's end cut short is left out,
and one that its beginning cut short still ends where it really ended.  A
capture too short to hold two such ends (a cell's ``capture_s``) is measured
whole and ``epochs`` is then 0.  If it holds one such end and the beginning
of the next execution, ``between`` describes the boundary: the seconds from
that end to that beginning, the idle seconds among them, and the programs
that began there (the next epoch's among them: the dispatches of an epoch).
"""

import bisect
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?$")
#: operations that only enclose others: their time is their children's
PARENT = re.compile(r"^(while|call|conditional)$")
TOP = 10
#: a program of many short operations leaves tens of thousands of gaps of
#: nanoseconds between them: only the longest are matched with the host's events
LABELLED = 100


def find_xplane(log_dir):
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path):
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as handle:
            return ProfileData.from_text_proto(handle.read())
    return ProfileData.from_file(path)


def events_of(plane, line_name):
    """[(start_ns, end_ns, name)] of one line of a plane, by start, a parent
    before the children it encloses."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events)
    out.sort(key=lambda event: (event[0], -event[1]))
    return out


def union_ns(intervals, lo, hi):
    """Nanoseconds of [lo, hi) covered by at least one interval."""
    covered, reach = 0.0, lo
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if end <= start:
            continue
        if start > reach:
            reach = start
        if end > reach:
            covered += end - reach
            reach = end
    return covered


def gaps_of(intervals, lo, hi):
    """[(start, end)] of [lo, hi) that no interval covers."""
    gaps, reach = [], lo
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if end <= start:
            continue
        if start > reach:
            gaps.append((reach, start))
        reach = max(reach, end)
    if reach < hi:
        gaps.append((reach, hi))
    return gaps


def epoch_module(modules):
    """Name of the program with the most device time: the epoch's."""
    total = {}
    for start, end, name in modules:
        total[name] = total.get(name, 0.0) + (end - start)
    return max(total, key=total.get) if total else None


def self_times(ops, lo, hi):
    """{name: ns} inside [lo, hi), each operation's own time: an enclosing
    operation is charged only what its children leave uncovered.  ``ops`` as
    ``events_of`` gives them."""
    charged, stack = {}, []  # stack of [end, name, own]

    def close(until):
        while stack and stack[-1][0] <= until:
            _, name, own = stack.pop()
            charged[name] = charged.get(name, 0.0) + max(0.0, own)

    for start, end, name in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        close(start)
        if stack:
            stack[-1][2] -= end - start
        stack.append([end, name, end - start])
    close(float("inf"))
    return charged


def op_name(text):
    """``%fusion.123 = f32[...] fusion(...)`` -> ``fusion.123``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_kind(text):
    """The HLO opcode of an operation's text: what follows the result's shape
    (``custom-call``, ``fusion``, ``all-reduce-start``).  A name without a
    text (a hand-made trace, another profiler) is its own kind."""
    _, found, rest = text.partition(" = ")
    if not found:
        return generic_name(text)
    match = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + _past_shape(rest))
    return match.group(1) if match else generic_name(text)


def _past_shape(rest):
    """Drop the result's shape, which may be a parenthesised tuple."""
    depth = 0
    for index, char in enumerate(rest):
        if char in "([{":
            depth += 1
        elif char in ")]}":
            depth -= 1
        elif char == " " and depth == 0:
            return rest[index:]
    return rest


def generic_name(text):
    """``%multiply_add_fusion.123 = ...`` -> ``multiply_add_fusion``:
    executions of one kind of operation add up."""
    return re.sub(r"[.\d]+$", "", op_name(text)) or text


def label_gaps(host_events, gaps):
    """For each of ``gaps`` (disjoint, by start) the host event that covers
    most of it (the shortest such, where several cover it equally), else
    ``unattributed``.  One pass over the host's events."""
    starts = [start for start, _ in gaps]
    best = [("unattributed", (0.0, 0.0))] * len(gaps)
    for s, e, name in host_events:
        index = max(0, bisect.bisect_right(starts, s) - 1)
        while index < len(gaps) and gaps[index][0] < e:
            overlap = min(e, gaps[index][1]) - max(s, gaps[index][0])
            key = (overlap, -(e - s))
            if overlap > 0 and key > best[index][1]:
                best[index] = (name, key)
            index += 1
    return [name for name, _ in best]


def reduce_planes(planes):
    """The numbers of one trace.  Per chip and as the mean over chips."""
    planes = list(planes)
    chips = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                   key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))
    host = [event for p in planes if p.name == HOST_PLANE
            for line in p.lines
            for event in ((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events)]
    per_chip, op_ns, gap_rows = [], {}, []
    for plane in chips:
        modules = events_of(plane, MODULE_LINE)
        ops = events_of(plane, OP_LINE) or modules
        epoch = epoch_module(modules)
        last_start = max((s for s, _, _ in modules), default=0)
        ends = [e for _, e, name in modules if name == epoch and e <= last_start]
        epochs, between = max(0, len(ends) - 1), None
        if epochs:
            lo, hi = ends[0], ends[-1]
        elif ops:  # a short capture: all of it
            lo, hi = ops[0][0], max(e for _, e, _ in ops)
            resumed = [s for s, _, name in modules
                       if ends and name == epoch and s >= ends[0]]
            if resumed:
                between = (ends[0], min(resumed))
        else:
            continue
        inside = [m for m in modules if lo <= m[0] < hi]
        busy = union_ns(ops, lo, hi)
        own = self_times(ops, lo, hi)
        kinds = {text: op_kind(text) for text in own}
        collective = sum(ns for text, ns in own.items()
                         if COLLECTIVE.match(kinds[text]))
        for text, ns in own.items():
            if not PARENT.match(kinds[text]):
                key = generic_name(text)
                op_ns[key] = op_ns.get(key, 0.0) + ns / len(chips)
        gaps = sorted(gaps_of(ops, lo, hi), key=lambda gap: gap[0] - gap[1])
        longest = sorted(gaps[:LABELLED])
        gap_rows += [(end - start, label) for (start, end), label
                     in zip(longest, label_gaps(host, longest))]
        if gaps[LABELLED:]:
            gap_rows.append((sum(end - start for start, end in gaps[LABELLED:]),
                             f"gaps shorter than the {LABELLED} longest"))
        per_chip.append({
            "plane": plane.name, "epoch_module": epoch,
            "epochs": epochs, "modules": len(inside),
            "op_events": len(ops),
            "window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
            "collective_s": collective / 1e9,
            "between": between and {
                "seconds": (between[1] - between[0]) / 1e9,
                "idle_s": (between[1] - between[0]
                           - union_ns(ops, *between)) / 1e9,
                "modules": sum(1 for m in modules
                               if between[0] <= m[0] <= between[1])},
            "named_s": {f"{kinds[text]} {op_name(text)}": ns / 1e9
                        for text, ns in own.items()
                        if not PARENT.match(kinds[text])}})
    if not per_chip:
        return None
    mean = lambda key: sum(c[key] for c in per_chip) / len(per_chip)
    gap_ns = {}
    for ns, label in gap_rows:
        gap_ns[label] = gap_ns.get(label, 0.0) + ns / len(per_chip)
    top = lambda table: [[name, ns / 1e9] for name, ns in sorted(
        table.items(), key=lambda kv: -kv[1])[:TOP]]
    between = None
    if all(c["between"] for c in per_chip):
        between = {key: sum(c["between"][key] for c in per_chip) / len(per_chip)
                   for key in ("seconds", "idle_s", "modules")}
    return {"chips": len(per_chip), "window_s": mean("window_s"),
            "busy_s": mean("busy_s"), "collective_s": mean("collective_s"),
            "epochs": per_chip[0]["epochs"], "between": between,
            "modules_per_epoch": (mean("modules") / per_chip[0]["epochs"]
                                  if per_chip[0]["epochs"]
                                  else between and between["modules"]),
            "epoch_module": per_chip[0]["epoch_module"],
            "per_chip": [{k: v for k, v in c.items() if k != "named_s"}
                         for c in per_chip],
            "named_s": _mean_tables([c["named_s"] for c in per_chip]),
            "breakdown": {"device_ops": top(op_ns), "idle_gaps": top(gap_ns)}}


def _mean_tables(tables):
    out = {}
    for table in tables:
        for name, value in table.items():
            out[name] = out.get(name, 0.0) + value / len(tables)
    return out


def reduce_file(path):
    return reduce_planes(load(path).planes)


def describe(path, names=12):
    """Print what a trace holds: planes, lines, counts, the commonest names."""
    for plane in load(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            count = {}
            for e in events:
                count[e.name] = count.get(e.name, 0) + 1
            span = ((events[-1].start_ns + events[-1].duration_ns
                     - events[0].start_ns) / 1e9) if events else 0.0
            print(f"  line {line.name!r}: {len(events)} events over "
                  f"{span:.3f} s")
            for name, n in sorted(count.items(), key=lambda kv: -kv[1])[:names]:
                print(f"      {n:7d} x {name[:140]}")


if __name__ == "__main__":
    describe(sys.argv[1])
    import json

    print(json.dumps(reduce_file(sys.argv[1]), indent=1)[:6000])
