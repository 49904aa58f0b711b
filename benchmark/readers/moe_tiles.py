"""How often the expert layers' tiled product reads a touched expert's
weights, from the two counters that the served block brings with its walk
over row tiles (``LatentMoELM.decode_spec``'s ``instruments``), as the driver
marks them at the window's two edges (``facts["marks"]``).  A program whose
grouped product walks no tiles, as the parent of the PR that added the
counters, or a block without experts, reads None."""

TILES = "serving_moe_tiles_total"
TOUCHED = "serving_moe_experts_touched_total"


def moe_tiles_per_expert(facts):
    """Row tiles that did work (each reads one expert's three matrices
    once) over the held experts that had at least one row in a call, both
    summed over the expert layers of every step and prefill and gained
    between the window's edges.  1.0: every touched expert's weights were
    streamed once a call."""
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    if TILES not in marks["close"]:
        return None
    # an instrument that is first touched inside the window is not at its
    # opening edge yet: it stood at nought there
    gained = lambda name: (marks["close"].get(name, 0.0)
                           - marks["open"].get(name, 0.0))
    touched = gained(TOUCHED)
    if not touched:
        return None
    return gained(TILES) / touched
