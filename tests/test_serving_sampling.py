"""The sampling picks its path from the batch's own knobs
(``serving/sampling.py::sampling_level``): an ``argmax`` alone while no active
slot samples, a draw from the scaled logits while none truncates, the sort
over the vocabulary otherwise.  The tokens may not depend on the path: the
one-path implementation that sorted every row on every call is kept here as
the plain reference, and every mix of knobs is held to it bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.serving.sampling import (
    filtered_logits,
    sample_one,
    sample_tokens,
    sampling_level,
)

SLOTS, VOCAB = 6, 97


def reference_one(logits, key, temperature, top_k, top_p):
    """The sampling as it was before it chose a path: sort, mask and draw
    for every row, and only then keep the argmax for a greedy one."""
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = filtered_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy_tok)


reference_tokens = jax.jit(jax.vmap(reference_one))


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(7)
    logits = jnp.asarray(3.0 * rng.normal(size=(SLOTS, VOCAB)), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(2**31 + i) for i in range(SLOTS)])
    return logits, keys


def knobs(temperature, top_k=None, top_p=None, active=None):
    return (np.asarray(temperature, np.float32),
            np.asarray(top_k if top_k is not None else [0] * SLOTS, np.int32),
            np.asarray(top_p if top_p is not None else [1.0] * SLOTS,
                       np.float32),
            np.asarray(active if active is not None else [1] * SLOTS, bool))


WARM = [0, 0.8, 0, 0, 1.3, 0]
# name -> (knobs, the level they put the batch at)
MIXES = {
    "all_greedy": (knobs([0] * SLOTS), 0),
    "greedy_with_knobs_that_mean_nothing": (
        knobs([0] * SLOTS, top_k=[5] * SLOTS, top_p=[0.5] * SLOTS), 0),
    "one_slot_samples_without_truncation": (
        knobs([0, 0, 0.8, 0, 0, 0]), 1),
    "top_k_at_or_over_the_vocabulary_truncates_nothing": (
        knobs(WARM, top_k=[0, VOCAB, 0, 0, VOCAB + 5, 0]), 1),
    "top_k_only": (knobs(WARM, top_k=[0, 5, 0, 0, 0, 0]), 2),
    "top_p_only": (knobs(WARM, top_p=[1, 1, 1, 1, 0.9, 1]), 2),
    "both": (knobs(WARM, top_k=[0, 9, 0, 0, 30, 0],
                   top_p=[1, 0.8, 1, 1, 0.95, 1]), 2),
    "every_slot_samples": (
        knobs([0.5, 0.8, 1.0, 1.3, 2.0, 0.1], top_k=[3, 0, 0, 50, 0, 1],
              top_p=[1, 0.5, 1, 0.9, 1, 1]), 2),
    # a freed slot's knobs may still say "sample": the level follows the
    # active slots only
    "inactive_slots_with_stale_knobs": (
        knobs(WARM, top_k=[0, 5, 0, 0, 0, 0], top_p=[1, 1, 1, 1, 0.9, 1],
              active=[1, 0, 1, 1, 0, 1]), 0),
    "an_inactive_truncating_slot_beside_an_active_plain_one": (
        knobs(WARM, top_k=[0, 5, 0, 0, 0, 0], active=[1, 0, 1, 1, 1, 1]), 1),
    "nothing_active": (knobs(WARM, top_k=[0, 5, 0, 0, 0, 0],
                             active=[0] * SLOTS), 0),
}


@pytest.fixture(scope="module")
def jitted():
    return jax.jit(sample_tokens)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_tokens_are_the_one_path_references(batch, jitted, mix):
    logits, keys = batch
    (temperature, top_k, top_p, active), _ = MIXES[mix]
    want = np.asarray(reference_tokens(logits, keys, temperature, top_k,
                                       top_p))
    got = np.asarray(jitted(logits, keys, temperature, top_k, top_p, active))
    assert got.dtype == np.int32 and got.shape == (SLOTS,)
    # an inactive slot's token is masked by the engine; the active ones are
    # the reference's, bit for bit
    np.testing.assert_array_equal(got[active], want[active])
    # without ``active`` every slot counts, and every token is the reference's
    np.testing.assert_array_equal(
        np.asarray(jitted(logits, keys, temperature, top_k, top_p)), want)


traced_level = jax.jit(lambda *knobs: sampling_level(*knobs, VOCAB))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_the_levels_rule_host_side_against_program_side(mix):
    """One rule, written once: on the host's ``numpy`` mirrors (the engine's
    counters) and on traced arrays inside a program it gives the same
    level."""
    arrays, want = MIXES[mix]
    host = sampling_level(*arrays, VOCAB)
    assert isinstance(host, np.integer) and int(host) == want
    assert int(traced_level(*arrays)) == want


def test_one_compile_for_every_mix(batch):
    """The knobs are data: after every mix above the jitted sampling holds
    one program."""
    logits, keys = batch
    # a function of its own: jit's cache is the wrapped function's
    jitted = jax.jit(lambda *args: sample_tokens(*args))
    for arrays, _ in MIXES.values():
        jitted(logits, keys, *arrays)
    assert jitted._cache_size() == 1


def _primitives(jaxpr):
    return [eqn.primitive.name for eqn in jaxpr.eqns]


def test_the_sort_stands_in_one_branch_only(batch):
    """The program's top level holds the predicate and one ``cond`` of three
    branches; the sort, the cumulative sum and the draw are inside
    branches, so a level that does not need them does not run them."""
    logits, keys = batch
    arrays, _ = MIXES["both"]
    jaxpr = jax.make_jaxpr(sample_tokens)(logits, keys, *arrays).jaxpr
    top = _primitives(jaxpr)
    assert top.count("cond") == 1
    assert not {"sort", "cumsum", "random_bits", "argmax"} & set(top)
    (cond,) = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "cond"]
    greedy, plain, sorted_ = [
        str(branch.jaxpr) for branch in cond.params["branches"]]
    assert "argmax" in greedy
    assert not any(name in greedy for name in ("sort", "cumsum", "random_"))
    assert "random_" in plain and "sort" not in plain
    assert "cumsum" not in plain
    assert "sort" in sorted_ and "cumsum" in sorted_


ROWS = {
    "greedy": (0.0, 0, 1.0, 0),
    "greedy_with_knobs": (0.0, 7, 0.5, 0),
    "plain": (0.8, 0, 1.0, 1),
    "top_k": (1.3, 6, 1.0, 2),
    "top_p": (0.9, 0, 0.8, 2),
    "both": (1.1, 9, 0.9, 2),
}


@pytest.mark.parametrize("row", sorted(ROWS))
def test_sample_one_on_scalars_for_the_prefill(batch, row):
    """The prefill's single row takes the same choice from its own
    request's knobs, as traced scalars of one program."""
    logits, keys = batch
    temperature, top_k, top_p, level = ROWS[row]
    scalars = (np.float32(temperature), np.int32(top_k), np.float32(top_p))
    assert int(sampling_level(*scalars, np.bool_(True), VOCAB)) == level
    one = jax.jit(lambda *args: sample_one(*args))
    for slot in range(SLOTS):
        got = one(logits[slot], keys[slot], *scalars)
        want = reference_one(logits[slot], keys[slot], *scalars)
        assert got.dtype == jnp.int32 and got.shape == ()
        assert int(got) == int(want)
    assert one._cache_size() == 1
    # plain Python numbers do as well (the public function's callers)
    assert int(sample_one(logits[0], keys[0], temperature, top_k, top_p)) \
        == int(reference_one(logits[0], keys[0], *scalars))
