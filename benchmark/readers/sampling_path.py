"""Which path the decode steps' sampling took, from the engine's always-on
counters (``facts["marks"]``: every instrument of the run's registry at the
window's two edges, under its own name).  A program whose sampling does not
choose a path, as the parent of the PR that added the counter, reads None."""

SAMPLED = "serving_decode_steps_sampled_total"
STEPS = "serving_decode_steps_total"


def decode_argmax_share(facts):
    """Decode steps in which no active slot sampled, so that the step took
    the argmax of its logits and nothing else (no sort over the vocabulary,
    no softmax, no draw), over all decode steps, both gained between the
    window's edges."""
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    if SAMPLED not in marks["close"]:
        return None  # a program that sorts on every step has no such counter
    # an instrument that is first touched inside the window is not at its
    # opening edge yet: it stood at nought there
    gained = lambda name: (marks["close"].get(name, 0.0)
                           - marks["open"].get(name, 0.0))
    steps = gained(STEPS)
    if not steps:
        return None
    return 100.0 * (1.0 - gained(SAMPLED) / steps)
