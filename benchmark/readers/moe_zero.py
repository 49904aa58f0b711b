"""Per-layer metrics of a block whose router has zero-compute (identity)
experts, from the two instruments that the served block brings itself for
them (``ShortcutMoELM.decode_spec``'s ``instruments``), as the driver marks
them at the window's two edges (``facts["marks"]``: a counter's value, a
histogram's ``(sum, count)``).  A program whose router has no such outputs,
as every block before the PR that added the instruments, reads None."""

ASSIGNED = "serving_moe_assignments_total"
ZERO = "serving_moe_assignments_zero_total"
REAL = "serving_moe_real_picks_max_over_mean"


def _edges(facts, name):
    """``(open, close)`` marks if both edges were marked and the program
    has the instrument ``name``, else None."""
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    if name not in marks["close"]:
        return None
    return marks["open"], marks["close"]


def moe_zero_share(facts):
    """Expert assignments of the window's live tokens (prompts' and decode
    steps') that met a zero-compute expert, over all of them, both gained
    between the window's edges: 100 x zero-compute / router outputs under
    even routing."""
    edges = _edges(facts, ZERO)
    if edges is None:
        return None
    # an instrument that is first touched inside the window is not at its
    # opening edge yet: it stood at nought there
    gained = lambda name: edges[1].get(name, 0.0) - edges[0].get(name, 0.0)
    if not gained(ASSIGNED):
        return None
    return 100.0 * gained(ZERO) / gained(ASSIGNED)


def moe_real_picks_max_over_mean(facts):
    """The window's decode steps' mean of: the most real (not zero-compute)
    experts that any live token picked over the live tokens' mean, averaged
    over the layers.  1 is every token doing the same work; the step waits
    for the token that picked the most."""
    edges = _edges(facts, REAL)
    if edges is None:
        return None
    first = edges[0].get(REAL, (0.0, 0))  # first touched inside the window
    total, count = (last - before
                    for before, last in zip(first, edges[1][REAL]))
    return total / count if count else None
