"""How often the serving loop dispatches ahead, from the engine's two
always-on counters (``facts["marks"]``: every instrument of the run's
registry at the window's two edges, under its own name).  A program that
does not count chained steps, as the parent of the PR that added the
counter, reads None."""

CHAINED = "serving_decode_steps_chained_total"
STEPS = "serving_decode_steps_total"


def decode_chained_share(facts):
    """Decode steps that were dispatched while the step before them had not
    yet been read by the host (their token and key inputs were that step's
    device outputs) over all decode steps, both gained between the window's
    edges."""
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    if CHAINED not in marks["close"]:
        return None  # a serial loop has no such counter
    # an instrument that is first touched inside the window is not at its
    # opening edge yet: it stood at nought there
    gained = lambda name: (marks["close"].get(name, 0.0)
                           - marks["open"].get(name, 0.0))
    steps = gained(STEPS)
    if not steps:
        return None
    return 100.0 * gained(CHAINED) / steps
