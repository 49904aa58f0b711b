"""Seeded serving traffic: one general generator, driven by a traffic file.

A traffic file of kind ``serve_open_loop`` holds parameters only:

* ``arrival``: ``"poisson"`` with ``rate_per_s`` (an open loop: requests are
  due on a schedule whether or not earlier ones have finished) or
  ``"closed"`` with ``clients`` and ``requests_per_client`` (each client
  sends its next request when its last has come back);
* ``ramp_s``: traffic before the window opens, so that the slots are as full
  at its start as in a steady state;
* ``prompt_tokens`` and ``output_tokens``: ``{"law": "log_uniform" |
  "uniform", "low": a, "high": b}``;
* ``tokens``: ``{"law": "zipf", "exponent": e}`` over the model's vocabulary
  (``datagen.zipf_tokens``' law).

**Every seed gets the same work in another order.**  The ``n`` lengths of a
law are its quantiles at ``(i + 1/2) / n`` and the gaps of a Poisson schedule
are the exponential law's quantiles at the same points, so their sums do not
depend on the seed; the seed permutes each list (and draws the tokens).  An
open loop's ramp and its window are two such blocks, each with its own lists:
the window holds the same number of arrivals, the same gaps and the same
lengths for every seed (with one list over both, the window's share of the
work swung by a tenth from seed to seed, and the tails with it: PERF.md,
PR 26).  A closed loop's requests are dealt round, and every round (one
request a client) is such a block, so that any stretch of whole rounds is
the same work whatever the seed.  Two runs then
differ by the order of the work, not by its amount: what a bound has to
cover is the system's noise and not the draw's.
"""

import numpy as np

import datagen


def quantile_lengths(law, count):
    """``count`` whole lengths: the law's quantiles at ``(i + 1/2) / count``."""
    u = (np.arange(count) + 0.5) / count
    low, high = float(law["low"]), float(law["high"])
    if law["law"] == "log_uniform":
        values = low * (high / low) ** u
    elif law["law"] == "uniform":
        values = low + (high - low) * u
    else:
        raise ValueError(f"unknown law of lengths {law['law']!r}")
    return np.rint(values).astype(np.int64)


def poisson_gaps(count, seconds):
    """``count`` gaps: the exponential law's quantiles at ``(i + 1/2) /
    count``, scaled so that they sum to ``seconds``."""
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u)
    return gaps * (seconds / gaps.sum())


def blocks(traffic, seconds):
    """``[(start_s, seconds, arrivals)]`` of an open loop: the ramp, then the
    window, each with ``rate x seconds`` arrivals (rounded)."""
    spans = ((0.0, float(traffic["ramp_s"])), (float(traffic["ramp_s"]), seconds))
    return [(start, length, round(traffic["rate_per_s"] * length))
            for start, length in spans if length > 0]


def request_count(traffic, seconds):
    if traffic["arrival"] == "poisson":
        return sum(count for _, _, count in blocks(traffic, seconds))
    if traffic["arrival"] == "closed":
        return traffic["clients"] * traffic["requests_per_client"]
    raise ValueError(f"unknown arrival {traffic['arrival']!r}")


def schedule(traffic, seed, seconds, *, vocab, max_len):
    """The run's requests, a function of the seed and the traffic file alone:
    ``[{"index", "due_s", "client", "prompt", "max_new"}]``.  ``due_s`` is
    seconds after the schedule's start (the ramp's), None in a closed loop,
    where ``client`` says whose request it is (dealt round)."""
    count = request_count(traffic, seconds)
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    lengths = lambda law, n: rng.permutation(quantile_lengths(traffic[law], n))
    if traffic["arrival"] == "poisson":
        # a block's arrivals are the partial sums of one gap more than it has
        # arrivals: the gap that is left over runs to the block's end
        parts = [(start + np.cumsum(rng.permutation(
                      poisson_gaps(n + 1, length)))[:n],
                  lengths("prompt_tokens", n), lengths("output_tokens", n))
                 for start, length, n in blocks(traffic, seconds)]
        due, prompts, outputs = (np.concatenate(column)
                                 for column in zip(*parts))
        clients = [None] * count
    else:
        # dealt round: every round gives each client one request, and holds
        # the law's ``clients`` quantiles, so any stretch of whole rounds is
        # the same work whatever the seed
        callers = traffic["clients"]
        rounds = lambda law: np.concatenate([
            lengths(law, callers) for _ in range(traffic["requests_per_client"])])
        prompts, outputs = rounds("prompt_tokens"), rounds("output_tokens")
        due = [None] * count
        clients = [i % callers for i in range(count)]
    if int(prompts.max()) + 1 >= max_len:
        raise ValueError("a prompt of this mix leaves no room for a token")
    outputs = np.minimum(outputs, max_len - prompts)  # what the model can hold
    law = traffic["tokens"]
    if law["law"] != "zipf":
        raise ValueError(f"unknown law of tokens {law['law']!r}")
    tokens, _ = datagen.zipf_tokens(
        count, int(rng.integers(0, 2**31)), seq=int(prompts.max()),
        vocab=vocab, exponent=law["exponent"])
    return [{"index": i, "due_s": None if due[i] is None else float(due[i]),
             "client": clients[i], "prompt": tokens[i, :prompts[i]].tolist(),
             "max_new": int(outputs[i])} for i in range(count)]
