"""Per-layer metrics read from the device trace as ``tracelib`` reduced it."""


def dispatches_per_epoch(facts):
    trace = facts["trace"]
    return trace and trace["modules_per_epoch"]


def device_idle_share(facts):
    """Over whole epochs: 1 - busy / window.  From a capture of the boundary
    between two epochs (a cell's ``capture_s``): the idle seconds between the
    two epoch programs, plus the programs' own idle rate (the rest of the
    capture's) over the rest of the epoch, over that epoch's period, which is
    the watcher's (the trace does not hold the epoch's other end).  Nothing
    from a capture that holds neither."""
    trace = facts["trace"]
    if not trace:
        return None
    if trace["epochs"]:
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    between, k, done = trace["between"], facts["traced_epoch"], facts["epoch_done"]
    if not between or k + 1 >= len(done) or None in (done[k], done[k + 1]):
        return None
    period = done[k + 1] - done[k]
    own_rate = ((trace["window_s"] - trace["busy_s"] - between["idle_s"])
                / (trace["window_s"] - between["seconds"]))
    idle = between["idle_s"] + own_rate * (period - between["seconds"])
    return 100.0 * idle / period
