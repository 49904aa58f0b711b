"""Per-layer metrics of the serving cells, read from the engine's always-on
instruments (its registry, read by the driver at the window's two edges:
``facts["marks"]``) and from the driver's own readings of ``engine.stats()``
every 50 ms inside the window (``facts["samples"]``).  Each reader returns
None where there is nothing to read."""


def _between(facts, name):
    """What a counter gained, or a histogram's (sum, count) gained, between
    the window's edges; None where an edge was not read."""
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    first, last = marks["open"][name], marks["close"][name]
    if isinstance(first, tuple):
        return last[0] - first[0], last[1] - first[1]
    return last - first


def _mean_ms(facts, name):
    gained = _between(facts, name)
    if not gained or not gained[1]:
        return None
    return 1e3 * gained[0] / gained[1]


def decode_step_ms(facts):
    """Host wall time of one decode step (dispatch, device, read-back)."""
    return _mean_ms(facts, "serving_token_latency_seconds")


def prefill_ms(facts):
    """Host wall time of one prefill dispatch with its read-back."""
    return _mean_ms(facts, "serving_prefill_seconds")


def _mean_stat(facts, key):
    samples = facts.get("samples")
    if not samples:
        return None
    return sum(stats[key] for _, stats in samples) / len(samples)


def slot_occupancy(facts):
    active = _mean_stat(facts, "active_slots")
    if not active:
        return None
    return 100.0 * active / _mean_stat(facts, "slots_total")


def queue_depth(facts):
    """An open loop's: requests waiting for a slot."""
    return _mean_stat(facts, "queue_depth")


def prefill_padding_share(facts):
    """Padding over prompt plus padding, of the prefills inside the window.
    The prompts' own tokens are the window's prefills' widths less their
    padding; the driver counts them from its records."""
    padded = _between(facts, "serving_prefill_padded_tokens")
    prompts = facts.get("prefilled_prompt_tokens")
    if padded is None or not prompts:
        return None
    return 100.0 * padded / (padded + prompts)


def goodput_share(facts):
    """The tokens made inside the window by the benchmark's count (answered
    requests, each for the share between its first token's stamp and its
    end's that falls inside) over the engine's own count of the tokens its
    prefills and decode steps made there: under 100 by what was made for
    requests that were never answered, and a check of the even spacing that
    ``serve_tokens_per_s`` assumes."""
    made = _between(facts, "serving_tokens_total")
    summary = facts.get("summary")
    if not made or not summary:
        return None
    return 100.0 * summary["generated_per_s"] * (
        facts["window"][1] - facts["window"][0]) / made


def first_token_p90_ms(facts):
    """An open loop's: due time to first token, 90th percentile."""
    summary = facts.get("summary")
    return summary and summary["ttft_p90_ms"]
