"""How far the decode step's bound by live length engages, from the engine's
two always-on counters (``facts["marks"]``: every instrument of the run's
registry at the window's two edges, under its own name).  A program that has
no such counters, as the parent of the PR that added them, reads None."""

READ = "serving_decode_kv_positions_read_total"
CAPACITY = "serving_decode_kv_positions_capacity_total"


def decode_kv_read_share(facts):
    """Cache positions that the single-token steps were told to cover (per
    active slot ``pos + 1`` rounded up to the block) over those they could
    (slots x max context), both gained between the window's edges."""
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    # an instrument that is first touched inside the window is not at its
    # opening edge yet: it stood at nought there
    gained = lambda name: (marks["close"].get(name, 0.0)
                           - marks["open"].get(name, 0.0))
    capacity = gained(CAPACITY)
    if not capacity:
        return None
    return 100.0 * gained(READ) / capacity
