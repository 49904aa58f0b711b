"""Per-layer metrics read from the host's clock and the program's counters.
Each reader takes the driver's ``facts`` and returns a number, or None when
there is nothing to read."""

import statistics


def epoch_ms_p90(facts):
    """An epoch that the watcher did not see on its own shares the interval
    with the one it was seen with, in equal parts."""
    done = facts["epoch_done"]
    intervals, last, pending = [], done[0], 0
    for t in done[1:]:
        pending += 1
        if t is not None and t > last:
            intervals += [(t - last) / pending] * pending
            last, pending = t, 0
    if len(intervals) < 2:
        return intervals[0] * 1e3 if intervals else None
    # the ninth of nine cut points, interpolated between the sorted values
    return statistics.quantiles(intervals, n=10, method="inclusive")[8] * 1e3


def compile_s(facts):
    return sum(seconds for _, _, seconds in facts["compile_events"])


def compiles_in_window(facts):
    opened, closed = facts["window"]
    return sum(1 for t, event, _ in facts["compile_events"]
               if event == facts["compile_backend"] and opened < t <= closed)


def mfu(facts):
    return (100.0 * facts["flops_per_item"] * facts["throughput"]
            / facts["peaks"]["bf16_flops_per_s"])


def peak_hbm_gb(facts):
    return facts["memory_peak_bytes"] / 1e9 or None
