"""Sampling beyond greedy: temperature / top-k / top-p, per-request seeds.

Every knob is a **traced scalar**, not a Python value: the serving engine
runs one jitted decode step for every request mix, so "this request samples
at temperature 0.8 with top_k 40, that one is greedy" must be data, never a
recompile (dklint DK102).  Greedy is the ``temperature <= 0`` limit and is
computed as an exact ``argmax`` — not a low-temperature softmax — so greedy
requests through the engine are token-identical to ``greedy_generate``.

Conventions (matching the common HF/vLLM semantics):

* ``temperature <= 0`` — greedy (argmax); the other knobs are ignored.
* ``top_k <= 0`` or ``>= vocab`` — no top-k truncation.
* ``top_p >= 1`` — no nucleus truncation; the smallest prefix of
  probability-sorted tokens with cumulative mass ``>= top_p`` is kept
  (the token that crosses the threshold is always kept).

Which path a batch takes (:func:`sampling_level`) is data too, decided once
for the whole batch from the knobs of its active slots, inside the program
(a ``lax.switch``: one compiled step, the branches not taken do not run):

* level 0, no active slot samples — the ``argmax`` alone: no sort over the
  vocabulary, no softmax, no cumulative sum, no Gumbel draw;
* level 1, some active slot samples and none truncates — the
  temperature-scaled logits straight into ``jax.random.categorical``;
* level 2, some active sampling slot has a top-k or a top-p — the sort,
  for every slot.

The tokens are the same at every level: a batch is at the level of its most
demanding active slot, and a lower level leaves out only what the knobs make
a no-op (both masks of :func:`filtered_logits` all true) or what the final
``where(temperature > 0, ...)`` would throw away.

Speculative decoding (:func:`speculative_verify`) builds on the same
filtered distributions: the acceptance test and the rejection-resample both
use the **modified** distribution (after temperature/top-k/top-p), which is
what makes draft-then-verify sampling exact for the filtered target
distribution (Leviathan et al., arXiv:2211.17192, applied per-knob).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "filtered_logits",
    "modified_probs",
    "sample_one",
    "sample_tokens",
    "sampling_level",
    "speculative_verify",
    "speculative_verify_tokens",
]


def _scaled(logits, temperature):
    """Temperature-scaled logits (the traced divide-by-zero is guarded even
    though the greedy branch wins the final where)."""
    return logits / jnp.where(temperature > 0, temperature, 1.0)


def filtered_logits(logits, temperature, top_k, top_p):
    """Temperature-scaled logits with the top-k / top-p mask applied
    (masked-out entries are ``-inf``).  ``logits [vocab]``; knobs are traced
    scalars.  This is the distribution-shaping half of :func:`sample_one`,
    shared with the speculative accept/resample path."""
    vocab = logits.shape[-1]

    scaled = _scaled(logits, temperature)

    desc = jnp.sort(scaled)[::-1]  # [vocab], descending

    # top-k: keep logits >= the k-th largest; k<=0 or k>=vocab disables
    k = jnp.clip(top_k, 1, vocab)
    kth = desc[k - 1]
    use_k = (top_k > 0) & (top_k < vocab)
    k_mask = jnp.where(use_k, scaled >= kth, True)

    # top-p over the sorted softmax: keep the smallest prefix with
    # cumulative mass >= top_p; (cum - p) < top_p keeps the crossing token
    probs = jax.nn.softmax(desc)
    cum = jnp.cumsum(probs)
    keep_sorted = (cum - probs) < top_p  # [vocab] in sorted order
    # map back by value: the threshold is the smallest kept sorted logit
    n_keep = jnp.sum(keep_sorted)
    p_thresh = desc[jnp.clip(n_keep - 1, 0, vocab - 1)]
    use_p = top_p < 1.0
    p_mask = jnp.where(use_p, scaled >= p_thresh, True)

    return jnp.where(k_mask & p_mask, scaled, -jnp.inf)


def modified_probs(logits, temperature, top_k, top_p):
    """The *modified* distribution the sampler actually draws from:
    ``softmax(filtered_logits(...))``.  The speculative acceptance test
    compares draft and target under their modified distributions."""
    return jax.nn.softmax(filtered_logits(logits, temperature, top_k, top_p))


def sampling_level(temperature, top_k, top_p, active, vocab):
    """What the sampling of a batch needs, from its knobs alone: 0, the
    ``argmax``; 1, a draw from the temperature-scaled logits; 2, the sort
    (some top-k or top-p truncates).  Only slots that are ``active`` and
    sample (``temperature > 0``) count: a greedy or an idle slot's ``top_k``
    and ``top_p`` mean nothing.  Written on operators and ``.any()`` alone,
    so the program (traced arrays) and the host's counter of the path taken
    (``numpy`` mirrors of the same arrays) share the one rule."""
    samples = active & (temperature > 0)
    truncates = samples & (((top_k > 0) & (top_k < vocab)) | (top_p < 1.0))
    return samples.any().astype("int32") + truncates.any().astype("int32")


def _draw(logits, key, temperature, shaped):
    """One row's token: a draw from the ``shaped`` logits, or the exact
    ``argmax`` of the row's own for a greedy row."""
    sampled = jax.random.categorical(key, shaped).astype(jnp.int32)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy_tok)


def _sample_plain(logits, key, temperature):
    """Level 1 for one row: what :func:`_sample_sorted` computes when
    neither mask of :func:`filtered_logits` truncates."""
    return _draw(logits, key, temperature, _scaled(logits, temperature))


def _sample_sorted(logits, key, temperature, top_k, top_p):
    """Level 2 for one row, right for every knob."""
    return _draw(logits, key, temperature,
                 filtered_logits(logits, temperature, top_k, top_p))


def sample_tokens(logits, keys, temperature, top_k, top_p, active=True):
    """One token for every slot of a batch: ``logits [slots, vocab]``,
    ``keys [slots]`` PRNG keys, per-slot scalar knob arrays.  Returns int32
    ``[slots]``.  The path is chosen once for the batch (under ``vmap`` a
    choice a row would become a select, and every path would run), from the
    knobs of the slots that are ``active`` (``[slots]`` bool; an idle
    slot's stale knobs ask for nothing)."""
    temperature, top_k, top_p = map(jnp.asarray, (temperature, top_k, top_p))
    level = sampling_level(temperature, top_k, top_p, active,
                           logits.shape[-1])
    return jax.lax.switch(
        level,
        (lambda *_: jnp.argmax(logits, axis=-1).astype(jnp.int32),
         lambda keys, t, *_: jax.vmap(_sample_plain)(logits, keys, t),
         lambda *knobs: jax.vmap(_sample_sorted)(logits, *knobs)),
        keys, temperature, top_k, top_p)


def sample_one(logits, key, temperature, top_k, top_p):
    """Sample one token id from ``logits [vocab]``; every argument after
    ``logits`` is a traced scalar.  Returns an int32 scalar.  A batch of
    one: the path follows the row's own knobs."""
    return sample_tokens(logits[None], key[None], *(
        jnp.asarray(knob)[None] for knob in (temperature, top_k, top_p)))[0]


def speculative_verify(logits, drafts, draft_probs, key, temperature, top_k,
                       top_p, speculate):
    """Judge one slot's ``m``-token speculative window.

    ``logits [m, vocab]`` are the target's logits where row ``i`` predicts
    the position ``drafts[i]`` was proposed for; ``draft_probs [m, vocab]``
    are the draft's *modified* distributions at those positions (same
    temperature/top-k/top-p filtering).  ``speculate`` is a traced bool —
    False collapses to the plain single-token path (sample row 0 exactly as
    the non-speculative decode step would), so opted-out slots ride the same
    program without semantic drift.

    Returns ``(tokens [m], count, accepted, new_key)``: emit
    ``tokens[:count]``; ``accepted`` counts kept draft tokens (the
    proposed/accepted telemetry).  There is deliberately **no bonus token**:
    on an all-accept window the emitted suffix is ``drafts`` itself, so the
    draft model's own cache — which already holds K/V for every proposed
    token — never develops a hole and needs no catch-up feeds.

    Semantics per mode:

    * greedy (``temperature <= 0``): accept while the draft matches the
      target argmax; every emitted token is a target argmax row, so the
      emitted stream is bitwise the non-speculative greedy stream.
    * stochastic: Leviathan et al. acceptance-rejection — accept ``d_i``
      with probability ``min(1, p(d_i)/q(d_i))``; on first rejection,
      resample from ``normalize(max(p - q, 0))``.
    """
    m = logits.shape[0]
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy_ok = drafts == targets

    # Opted-out slots consume the same (key -> next_key, subkey) chain as
    # the non-speculative engine, so a request's sampled tokens don't depend
    # on its neighbours' opt-in.  The speculative keys derive from the fresh
    # subkey `next_plain` — never from `key` itself: under partitionable
    # threefry (the default in newer JAX), split(key, n)[:2] == split(key),
    # so re-splitting the parent would make the first accept-uniform reuse
    # the plain sampling key exactly (correlated accept/resample streams —
    # the DK111 lineage rule pins this).
    next_plain, sub_plain = jax.random.split(key)
    spec_keys = jax.random.split(next_plain, 2 * m + 1)  # [next, m accepts, m resamples]

    p = jax.vmap(modified_probs, in_axes=(0, None, None, None))(
        logits, temperature, top_k, top_p)  # [m, vocab]
    p_d = jnp.take_along_axis(p, drafts[:, None], axis=1)[:, 0]
    q_d = jnp.take_along_axis(draft_probs, drafts[:, None], axis=1)[:, 0]
    u = jax.vmap(lambda k: jax.random.uniform(k))(spec_keys[1:m + 1])
    # u < p/q, written mult-form so q(d)=0 (never proposed, but numerically
    # possible) accepts iff p(d) > 0 instead of dividing by zero
    stoch_ok = u * q_d < p_d

    residual = jnp.maximum(p - draft_probs, 0.0)
    total = residual.sum(axis=-1, keepdims=True)
    # p == q makes the residual empty — but then rejection has probability
    # ~0; fall back to p so the categorical below stays well-defined
    residual = jnp.where(total > 0, residual / total, p)
    resampled = jax.vmap(
        lambda k, pr: jax.random.categorical(k, jnp.log(pr))
    )(spec_keys[m + 1:], residual).astype(jnp.int32)

    ok = jnp.where(temperature > 0, stoch_ok, greedy_ok)
    lead = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))  # leading accepts
    count = jnp.minimum(lead + 1, m)  # +1 = the correction/final token
    accepted = jnp.minimum(lead, count)
    out = jnp.where(temperature > 0, jnp.where(ok, drafts, resampled), targets)

    plain = _sample_sorted(logits[0], sub_plain, temperature, top_k, top_p)
    out = jnp.where(speculate, out, out.at[0].set(plain))
    count = jnp.where(speculate, count, 1).astype(jnp.int32)
    accepted = jnp.where(speculate, accepted, 0).astype(jnp.int32)
    new_key = jnp.where(speculate, spec_keys[0], next_plain)
    return out, count, accepted, new_key


def speculative_verify_tokens(logits, drafts, draft_probs, keys, temperature,
                              top_k, top_p, speculate):
    """Vmapped :func:`speculative_verify` over the slot batch: ``logits
    [slots, m, vocab]``, ``drafts [slots, m]``, ``draft_probs [slots, m,
    vocab]``, per-slot keys/knobs/opt-in."""
    return jax.vmap(speculative_verify)(
        logits, drafts, draft_probs, keys, temperature, top_k, top_p,
        speculate)
