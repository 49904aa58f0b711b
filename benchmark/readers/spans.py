"""Per-layer metrics read from the program's own epoch-grain spans.

``distkeras_tpu.telemetry`` records a handful of spans an epoch into its
flight-recorder ring whether or not telemetry is switched on (the benchmark
leaves it off), with absolute ``time.perf_counter()`` times: the watcher's
stamps in ``facts["epoch_done"]`` are on that clock, in this process.  The
readers take the ring after ``train()`` has returned, keep the epochs
``1..E-1`` (epoch 0 compiles) and return medians over them, in milliseconds.
A program without such a ring (a parent of the PR that brought it) or a ring
without the spans gives ``None``: the metric is left out of the line.

The spans (``distkeras_tpu/telemetry/trace.py``), all stamped with the
``epoch`` in whose iteration of the training loop they were opened:
``epoch_arrays`` (the gather), ``h2d`` (the transfer's enqueue),
``h2d_transfer`` (entry of ``shard_batches`` until the rows are ready on the
device), ``dispatch`` (the call that enqueues the epoch program),
``device_epoch`` (its return until the epoch's losses are ready) and
``stats_wait`` (the training thread blocked on the previous epoch's losses).
One job a process: an earlier fit's spans in the same ring would be taken for
this one's.  The two spans that end on the device come from readiness probes;
one the program lost (``telemetry.trace.probes_lost``) leaves its epoch out of
the medians, so ``feed_gap_ms``'s note line carries that count.
"""

import statistics

import harness

#: what the host does for an epoch, in the order a gap's seconds are given
#: to them: the training thread's own spans first (they never overlap), then
#: the transfer that goes on after its enqueue has returned
COVER = ("epoch_arrays", "h2d", "dispatch", "stats_wait", "h2d_transfer")
#: below this much gap an epoch there is nothing to attribute
LEAST_GAP_S = 1e-3


def _table(facts):
    """``{name: {epoch: [(t0, t1), ...]}}`` over the epochs 1..E-1, or None
    where the program has no ring of timed spans or the ring none of these."""
    try:
        from distkeras_tpu import telemetry
    except ImportError:
        return None
    take = getattr(telemetry.flightdeck.recorder, "spans", None)
    if take is None:
        return None
    last = facts["job"]["epochs"] - 1
    table = {}
    for span in take():
        epoch = span["attrs"].get("epoch")
        if isinstance(epoch, int) and 1 <= epoch <= last:
            table.setdefault(span["name"], {}).setdefault(epoch, []).append(
                (span["t0"], span["t1"]))
    return table or None


def _median_ms(facts, name):
    """Median over the epochs of the seconds an epoch spent under ``name``."""
    table = _table(facts)
    if not table or name not in table:
        return None
    return 1e3 * statistics.median(
        sum(t1 - t0 for t0, t1 in spans) for spans in table[name].values())


def gather_ms(facts):
    return _median_ms(facts, "epoch_arrays")


def h2d_ms(facts):
    return _median_ms(facts, "h2d_transfer")


def dispatch_ms(facts):
    return _median_ms(facts, "dispatch")


def host_slack_ms(facts):
    return _median_ms(facts, "stats_wait")


def _gaps(table):
    """``{epoch: (lo, hi)}`` for every epoch e >= 2 whose spans are whole:
    from the end of ``device_epoch(e-1)`` (the device has finished the last
    epoch) to the later of the ends of ``h2d_transfer(e)`` and
    ``dispatch(e)`` (it has both its rows and its program); ``hi < lo``
    where the device did not wait."""
    end = lambda name, epoch: max(t1 for _, t1 in table[name][epoch])
    gaps = {}
    for epoch in sorted(table.get("dispatch", {})):
        if (epoch >= 2 and epoch in table.get("h2d_transfer", {})
                and epoch - 1 in table.get("device_epoch", {})):
            gaps[epoch] = (end("device_epoch", epoch - 1),
                           max(end("h2d_transfer", epoch),
                               end("dispatch", epoch)))
    return gaps


def _split(table, epoch, lo, hi):
    """The seconds of ``[lo, hi]``, the gap before ``epoch``, under each span
    of COVER, each second given once, to the first name of COVER that covers
    it; what none covers is ``unattributed``.  Only spans of that epoch's
    iteration and of earlier ones count: the next iteration begins in the
    gap, and its gather is what the gap delays, not what it waits for."""
    free, split = [(lo, hi)] if hi > lo else [], {}
    for name in COVER:
        for of_epoch, spans in table.get(name, {}).items():
            for t0, t1 in spans if of_epoch <= epoch else ():
                left = []
                for a, b in free:
                    c, d = max(a, t0), min(b, t1)
                    if c >= d:
                        left.append((a, b))
                        continue
                    split[name] = split.get(name, 0.0) + d - c
                    left += [(x, y) for x, y in ((a, c), (d, b)) if y > x]
                free = left
    split["unattributed"] = sum(b - a for a, b in free)
    return split


def _gap_rows(facts):
    """``{epoch: (seconds of gap, its split)}``, or None without the spans."""
    table = _table(facts)
    gaps = table and _gaps(table)
    if not gaps:
        return None
    return {epoch: (max(0.0, hi - lo), _split(table, epoch, lo, hi))
            for epoch, (lo, hi) in gaps.items()}


def feed_gap_ms(facts):
    """The device's wait for its rows, on the program's clock; a note line
    gives every epoch's gap with its split by span and, beside the traced
    epoch's, the device trace's own reading of the same boundary."""
    rows = _gap_rows(facts)
    if not rows:
        return None
    per_epoch = {epoch: {"gap_ms": 1e3 * gap,
                         "split_ms": {name: 1e3 * seconds for name, seconds
                                      in split.items() if seconds}}
                 for epoch, (gap, split) in rows.items()}
    from distkeras_tpu import telemetry
    note = {"epochs": per_epoch,
            "probes_lost": getattr(telemetry.trace, "probes_lost", None)}
    traced, trace = facts.get("traced_epoch"), facts.get("trace")
    if traced is not None and traced + 1 in per_epoch:
        # the capture holds the end of epoch ``traced`` and the beginning of
        # the next: the gap before epoch ``traced + 1``
        note["traced_gap_epoch"] = traced + 1
        note["traced_gap_ms"] = per_epoch[traced + 1]["gap_ms"]
        if trace and trace.get("between"):
            note["trace_between_ms"] = 1e3 * trace["between"]["seconds"]
    harness.note(feed_gap=note)
    return 1e3 * statistics.median(gap for gap, _ in rows.values())


def gap_unattributed_share(facts):
    """Of the seconds of those gaps, the share under no span of COVER."""
    rows = _gap_rows(facts)
    if not rows:
        return None
    total = sum(gap for gap, _ in rows.values())
    if total < LEAST_GAP_S * len(rows):
        return None
    return 100.0 * sum(split["unattributed"]
                       for _, split in rows.values()) / total
