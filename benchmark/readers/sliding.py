"""Per-layer metrics of a block of sliding-window and global layers, from the
four counters that the served block brings itself for them
(``WindowMoELM.decode_spec``'s ``instruments``), as the driver marks them at
the window's two edges (``facts["marks"]``: a counter's value).  A program
that has no such counters, as every block before the PR that added them,
reads None."""

READ = "serving_sliding_positions_read_total"
LIVE = "serving_sliding_positions_live_total"
PAIRS = "serving_prefill_attn_pairs_total"
CAUSAL = "serving_prefill_attn_causal_pairs_total"


def _share(facts, part, whole):
    """100 x what ``part`` gained between the window's edges over what
    ``whole`` gained, or None where either edge or the instrument is
    missing, or ``whole`` gained nothing."""
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    if whole not in marks["close"]:
        return None
    # an instrument that is first touched inside the window is not at its
    # opening edge yet: it stood at nought there
    gained = lambda name: (marks["close"].get(name, 0.0)
                           - marks["open"].get(name, 0.0))
    if not gained(whole):
        return None
    return 100.0 * gained(part) / gained(whole)


def sliding_kv_read_share(facts):
    """Key positions that the decode steps' sliding-window reads covered
    (per step, live slot and sliding layer: the window) over those a read
    of the whole context would have covered (``pos + 1``): about 100 if the
    step read whole contexts in the sliding layers."""
    return _share(facts, READ, LIVE)


def prefill_attn_pairs_share(facts):
    """Query-key pairs that the prefills' attention scored, as its blocks
    lay them, over a dense causal attention's over the same padded widths
    and layers: about 100 if the sliding layers' band were lost."""
    return _share(facts, PAIRS, CAUSAL)
