"""Per-layer metrics of an expert model's serving cell, from the counters
that the served block brings itself (``LatentMoELM.decode_spec``'s
``instruments``) and the engine's gauge of the paged state's size, as the
driver marks them at the window's two edges (``facts["marks"]``: a counter's
or a gauge's value, a histogram's ``(sum, count)``).  A program that has no
such instrument, as the parent of the PR that added them or a block without
experts, reads None."""

ASSIGNED = "serving_moe_assignments_total"
HELD = "serving_moe_assignments_held_total"
LOAD = "serving_moe_expert_load_max_over_mean"
STATE = "serving_state_per_position_bytes"


def _edges(facts):
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    return marks["open"], marks["close"]


def moe_held_share(facts):
    """Expert assignments of the window's live tokens (prompts' and decode
    steps') that met an expert held on this chip, over all of them: 100 x
    held / experts under even routing."""
    edges = _edges(facts)
    if edges is None or ASSIGNED not in edges[1]:
        return None
    # an instrument that is first touched inside the window is not at its
    # opening edge yet: it stood at nought there
    gained = lambda name: edges[1].get(name, 0.0) - edges[0].get(name, 0.0)
    if not gained(ASSIGNED):
        return None
    return 100.0 * gained(HELD) / gained(ASSIGNED)


def moe_load_max_over_mean(facts):
    """The window's decode steps' mean of: the fullest held expert's
    assignments over the held experts' mean, averaged over the expert
    layers.  1 is an even load; the grouped product's longest group is this
    many times the mean."""
    edges = _edges(facts)
    if edges is None or LOAD not in edges[1]:
        return None
    first = edges[0].get(LOAD, (0.0, 0))
    total, count = (last - before for before, last in zip(first, edges[1][LOAD]))
    return total / count if count else None


def state_bytes_per_position(facts):
    """Bytes of paged state that one position keeps over all layers: the
    served block's declared rows in the pools' type."""
    edges = _edges(facts)
    if edges is None:
        return None
    return edges[1].get(STATE) or None
