"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it finished, drawn from
the seed and with the longest in it (``requests``: as many as a window
finishes, so in effect all of them), is run through the plain reference
(``reference_lm``: the full context in float32 at ``highest`` precision, no
cache, the served tokens teacher-forced).  At every generated position the
served token's reference logit is held against the reference's best.  Two
numbers of those gaps each have a limit in the configuration's
``serving.correct`` group, with the readings they were set from:

* ``gap_widest``, the widest gap: a wrong page, mask or position, or a token
  altered where it is produced, lies far below the reference's best;
* ``gap_fourth_mean``, the mean of the gaps' fourth powers over all compared
  tokens:
  rounding flips a near tie with a probability, and by a gap, that both grow
  with the size of the rounding error, so a moment of the gaps grows with a
  power of it and tells a computation in a lower precision (the control:
  the reference in bfloat16) from the program's, which the widest gap of a
  few thousand tokens and their mean do not (PERF.md section 2).

Logits and not tokens: with seeded weights the largest logit changes on
rounding, so a near tie cannot fail a sound run.
"""

import numpy as np

import reference_lm


def sample(finished, seed, count):
    """``count`` of the finished records, drawn from the seed, the longest
    (prompt and served tokens together) always among them."""
    usable = [r for r in finished if r["served"]]
    if not usable:
        return []
    longest = max(usable, key=lambda r: (r["prompt_tokens"] + r["tokens"],
                                         -r["index"]))
    rest = [r for r in usable if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    drawn = rng.permutation(len(rest))[:max(0, count - 1)]
    return [longest] + [rest[i] for i in sorted(drawn)]


def read_gaps(weights, requests, records, width, control_dtype=None):
    """Every sampled request's gaps, joined: ``(program's, control's)``; the
    control's is None without ``control_dtype``."""
    prompts = {r["index"]: r["prompt"] for r in requests}
    program, control = [], []
    for record in records:
        got = reference_lm.served_gaps(
            weights, prompts[record["index"]], record["served"], width,
            control_dtype)
        if control_dtype is None:
            program.append(got)
        else:
            program.append(got[0])
            control.append(got[1])
    join = lambda parts: np.concatenate(parts) if parts else np.zeros(0)
    return join(program), (join(control) if control_dtype else None)


#: the numbers that have a limit, ``<name>_at_most`` in the rules
COMPARED = ("gap_widest", "gap_fourth_mean")


def readings(gaps):
    """What is read from one array of gaps (float64 on the host); the first
    two have limits, the rest stand on a note beside them."""
    if not gaps.size:
        return {name: None for name in COMPARED}
    gaps = np.asarray(gaps, np.float64)
    return {"gap_widest": float(gaps.max()),
            "gap_fourth_mean": float((gaps ** 4).mean()),
            "gap_mean": float(gaps.mean()),
            "gap_square_mean": float((gaps ** 2).mean()),
            "gap_cube_mean": float((gaps ** 3).mean()),
            "off_best_share": float((gaps > 0).mean()),
            "over_0.02_share": float((gaps > 0.02).mean())}


def judge(gaps, rules):
    """``({name: {"value", "limit"}}, reasons)`` for one array of gaps."""
    read = readings(gaps)
    compared = {name: {"value": read[name], "limit": rules[name + "_at_most"]}
                for name in COMPARED}
    compared["tokens_compared"] = {"value": int(gaps.size),
                                   "limit": rules["tokens_at_least"]}
    reasons = []
    if gaps.size < rules["tokens_at_least"]:
        reasons.append(f"{gaps.size} served tokens to compare, the rule "
                       f"asks for {rules['tokens_at_least']}")
    for name in COMPARED:
        entry = compared[name]
        if entry["value"] is None or not entry["value"] <= entry["limit"]:
            reasons.append(f"{name} {entry['value']} over its limit "
                           f"{entry['limit']}")
    return compared, reasons


def judge_serving(weights, requests, finished, seed, rules, *, width):
    """``(compared, reasons, the other readings)`` of one run."""
    records = sample(finished, seed, rules["requests"])
    gaps, _ = read_gaps(weights, requests, records, width)
    read = readings(gaps)
    return judge(gaps, rules) + (dict(
        {k: v for k, v in read.items() if k not in COMPARED},
        requests_compared=len(records)),)
