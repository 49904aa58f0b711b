"""``JambaLM`` (Mamba-1 and grouped-query attention layers, Jamba's block) at
a small size on the CPU, in float32 on seeded weights, against the
benchmark's plain reference (``benchmark/reference_jamba.py``, one file,
loaded by path as a run loads it): the full forward, near variants of the
Mamba mixer told apart from it, the layers' kinds, the tied head, the
weights' layout and Mamba's own initialisation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import JambaLM

#: hidden 64 (d_inner 128), 4 query heads over 1 key-value head of 16, a
#: state of 16, dt rank 8; layers: Mamba, Mamba, attention, Mamba (the
#: published rule i % period == offset at period 4, offset 2)
TINY = dict(
    vocab_size=97, max_len=64, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=1, intermediate_size=128,
    attn_layer_period=4, attn_layer_offset=2, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8, rms_norm_eps=1e-6)

_LOADED = {}


@pytest.fixture
def reference(harness):
    """One instance for the session: its jitted pieces compile once."""
    if not _LOADED:
        _LOADED["module"] = harness.load_module(".", "reference_jamba")
    _LOADED["module"].configure(**TINY)
    return _LOADED["module"]


@pytest.fixture(scope="module")
def lm():
    model = JambaLM(**TINY)
    return model, model.init(jax.random.PRNGKey(0))


def _tokens(seed, length):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], length)


def test_full_forward_equals_the_reference(lm, reference):
    """Every layer's equations over 40 positions.  The tolerance is
    float32's: both sides compute in float32 on the CPU and differ by the
    order of their sums alone (logits of order 4)."""
    model, params = lm
    tokens = _tokens(1, 40)
    want = reference._reference_logits(params, jnp.asarray(tokens))
    got = model(params, tokens[None])[0]
    assert got.shape == (40, TINY["vocab_size"])
    np.testing.assert_allclose(got, want, atol=3e-5)


def _mamba_changed(params, **change):
    """The weights with each Mamba layer's named vectors replaced by
    ``change[name](vector)``."""
    out = jax.tree.map(lambda a: a, params)
    for layer in out["layers"]:
        if "A_log" in layer:
            layer.update({name: f(layer[name]) for name, f in change.items()})
    return out


VARIANTS = {
    "no_skip": {"D": jnp.zeros_like},
    "conv_without_bias": {"conv_b": jnp.zeros_like},
    "decay_of_one": {"A_log": jnp.zeros_like},
    "inner_norms_doubled": {name: lambda w: 2 * w
                            for name in ("dt_norm", "b_norm", "c_norm")},
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_a_near_variant_of_the_mixer_fails_the_comparison(lm, reference,
                                                          variant):
    """The reference against a mixer that drops ``D x``, the convolution's
    bias, the decay's spread over the states (``A = -1`` everywhere) or
    weighs the inner norms twice: the logits lie a thousand times the
    comparison's tolerance apart."""
    model, params = lm
    tokens = _tokens(1, 40)
    want = reference._reference_logits(params, jnp.asarray(tokens))
    got = model(_mamba_changed(params, **VARIANTS[variant]), tokens[None])[0]
    assert float(jnp.abs(got - want).max()) > 3e-2


def test_the_layers_kinds_follow_the_published_rule():
    """Layer i is attention iff i % attn_layer_period == attn_layer_offset:
    at the published 28, 14 and 7 that is layers 7 and 21, 26 Mamba."""
    model = JambaLM(vocab_size=11, max_len=16)
    kinds = [model.is_attention(i) for i in range(28)]
    assert [i for i, a in enumerate(kinds) if a] == [7, 21]
    assert model.mamba_layers == 26
    assert (model.d_inner, model.head_dim) == (5120, 128)


def test_the_published_parameter_count():
    """3,029,337,472 parameters, as the row's arithmetic has them (a Mamba
    layer 104,161,472, an attention layer 76,682,240, the tied embedding
    167,772,160, the final norm 2,560)."""
    model = JambaLM(vocab_size=65536, max_len=2560)
    shapes = model.param_shapes()
    count = lambda keys: sum(int(np.prod(shapes[k][0])) for k in keys)
    layer = lambda i: [k for k in shapes if k[:2] == ("layers", i)]
    assert count(layer(0)) == 104_161_472
    assert count(layer(7)) == 76_682_240
    assert count(shapes) == 3_029_337_472
    assert ("head",) not in shapes  # tied: the embedding is the head


def test_the_head_is_the_embedding_transposed(lm):
    """Logits move with the embedding's rows: a row scaled by 0 gives its
    token a logit of 0 at every position."""
    model, params = lm
    tokens = _tokens(2, 12)
    changed = dict(params, embed=params["embed"].at[5].set(0.0))
    got = model(changed, tokens[None])[0]
    np.testing.assert_array_equal(got[:, 5], 0.0)
    assert float(jnp.abs(model(params, tokens[None])[0][:, 5]).max()) > 0


def test_mambas_own_initialisation(lm):
    """A_log is log(1 .. 16) for every channel, softplus(dt_bias) lies in
    [0.001, 0.1], D is one; the weights' layout is the reference's."""
    model, params = lm
    layer = params["layers"][0]
    np.testing.assert_allclose(layer["A_log"][:, 3],
                               np.log(np.arange(1, 17)), rtol=1e-6)
    steps = jax.nn.softplus(layer["dt_bias"])
    assert 0.001 <= float(steps.min()) and float(steps.max()) <= 0.1 + 1e-6
    np.testing.assert_array_equal(layer["D"], 1.0)
    assert set(layer) == {"input_norm", "ffn_norm", "gate", "up", "down",
                          "in_proj", "conv_w", "conv_b", "x_proj", "dt_norm",
                          "b_norm", "c_norm", "dt_proj", "dt_bias", "A_log",
                          "D", "out_proj"}
    assert set(params["layers"][2]) == {"input_norm", "ffn_norm", "gate",
                                        "up", "down", "q", "k", "v", "o"}


def test_the_reference_weights_have_the_programs_layout(reference):
    weights = reference.make_weights(3, **TINY)
    params = JambaLM(**TINY).init(jax.random.PRNGKey(0))
    shape = lambda tree: jax.tree.map(lambda a: a.shape, tree)
    assert shape(weights) == shape(params)
    layer = weights["layers"][0]
    assert layer["in_proj"].dtype == jnp.bfloat16
    assert layer["A_log"].dtype == jnp.float32
    np.testing.assert_allclose(layer["A_log"][:, 0], np.log(np.arange(1, 17)),
                               rtol=1e-6)


@pytest.mark.parametrize("sizes", [
    {"mamba_conv_bias": False}, {"mamba_proj_bias": True},
    {"tie_word_embeddings": False}, {"hidden_size": 96}])
def test_what_is_not_built_is_refused(sizes):
    with pytest.raises(ValueError):
        JambaLM(**dict(TINY, **sizes))
