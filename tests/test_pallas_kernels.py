"""Pallas kernel correctness: flash attention vs the jnp reference.

Runs under the Pallas interpreter on the CPU backend (conftest forces
JAX_PLATFORMS=cpu), which executes the identical kernel code the TPU compiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops.pallas import flash_attention
from distkeras_tpu.parallel.ring import local_attention


def _rand_qkv(rng, b, l, h, d, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    shape = (b, l, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _f32(x):
    return x.astype(jnp.float32)


# float32: the kernels against the same arithmetic in jnp.  bfloat16: against
# the reference on the float32 copies of the inputs, at bfloat16's tolerance;
# lengths and blocks chosen so that one call crosses skipped, unmasked,
# diagonal and padded blocks, with square blocks (everything decided while
# tracing) and oblong ones (the diagonal's place known only at run time)
_BF16_CASES = [
    pytest.param(jnp.bfloat16, l, blocks, id=f"bfloat16-{l}-{blocks[0]}x{blocks[1]}")
    for l in (100, 256) for blocks in ((64, 64), (64, 128), (128, 64))
]
_TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
        jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}
_GRAD_TOL = {jnp.float32: dict(atol=3e-5, rtol=3e-4),
             jnp.bfloat16: dict(atol=5e-2, rtol=5e-2)}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,l,blocks", [
    pytest.param(jnp.float32, 64, (64, 64), id="64"),
    pytest.param(jnp.float32, 100, (64, 64), id="100"),  # exercises seq padding
] + _BF16_CASES)
def test_forward_matches_reference(causal, dtype, l, blocks):
    q, k, v = _rand_qkv(jax.random.key(0), 2, l, 2, 32, dtype)
    out = flash_attention(q, k, v, causal, *blocks, True)
    assert out.dtype == dtype
    ref = local_attention(_f32(q), _f32(k), _f32(v), causal=causal)
    np.testing.assert_allclose(np.asarray(_f32(out)), np.asarray(ref),
                               **_TOL[dtype])


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,l,blocks", [
    pytest.param(jnp.float32, 64, (32, 32), id="float32"),
] + _BF16_CASES)
def test_gradients_match_reference(causal, dtype, l, blocks):
    q, k, v = _rand_qkv(jax.random.key(1), 1, l, 2, 16, dtype)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, *blocks, True)
        return jnp.sum(jnp.sin(_f32(o)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(local_attention(q, k, v, causal=causal)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(_f32(q), _f32(k), _f32(v))
    for gf, gr in zip(g_flash, g_ref):
        assert gf.dtype == dtype
        np.testing.assert_allclose(np.asarray(_f32(gf)), np.asarray(gr),
                                   **_GRAD_TOL[dtype])


# (heads, head width): two and four heads to a block's 128 lanes, an odd head
# count (one head a block), and a head that fills the lanes alone
@pytest.mark.parametrize("h,d", [(2, 64), (4, 32), (3, 32), (1, 128)])
@pytest.mark.parametrize("causal", [False, True])
def test_default_blocks_match_reference(causal, h, d):
    """No block arguments: what callers run.  300 pads to 384, one block with
    the diagonal through it and padded keys at its end."""
    q, k, v = _rand_qkv(jax.random.key(5), 1, 300, h, d)
    do = jax.random.normal(jax.random.key(6), q.shape)
    flash = lambda q, k, v: flash_attention(q, k, v, causal, interpret=True)
    ref = lambda q, k, v: local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    g_flash = jax.grad(lambda *a: jnp.sum(flash(*a) * do), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) * do), (0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=3e-5, rtol=3e-4)


def test_cross_lengths_match_reference():
    """More keys than queries and the reverse, no causal mask."""
    for lq, lk in ((72, 200), (200, 72)):
        ks = jax.random.split(jax.random.key(lq), 3)
        q = jax.random.normal(ks[0], (1, lq, 2, 32))
        k, v = (jax.random.normal(kk, (1, lk, 2, 32)) for kk in ks[1:])
        out = flash_attention(q, k, v, False, 64, 64, True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(local_attention(q, k, v)),
                                   atol=2e-5, rtol=2e-5)


def _kernel_dots(jaxpr):
    """The ``dot_general`` equations of each ``pallas_call`` under ``jaxpr``,
    in program order: one list a kernel."""
    def subjaxprs(eqn):
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) else [value]:
                item = getattr(item, "jaxpr", item)
                if hasattr(item, "eqns"):
                    yield item

    def dots(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in subjaxprs(eqn):
                yield from dots(sub)

    def kernels(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield list(dots(eqn.params["jaxpr"]))
            else:
                for sub in subjaxprs(eqn):
                    yield from kernels(sub)

    return list(kernels(jaxpr))


# the kernel's place among the pallas_calls of forward + backward, and the
# matrix products one tile of it makes
@pytest.mark.parametrize("kernel,products", [
    pytest.param(0, 2, id="forward"), pytest.param(1, 3, id="dq"),
    pytest.param(2, 4, id="dkv")])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_matmul_operands_keep_the_inputs_dtype(kernel, products, dtype):
    """The MXU gets what the caller gave: with bfloat16 inputs no product
    inside the kernels has a float32 operand (an upcast buys no accuracy, a
    bf16 x bf16 product is exact in float32); with float32 inputs every
    product is float32 x float32.  Every product accumulates in float32."""
    q = jnp.zeros((1, 128, 2, 32), dtype)
    loss = lambda q, k, v: _f32(flash_attention(q, k, v, True, 64, 64, True)).sum()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr
    per_kernel = _kernel_dots(jaxpr)
    assert len(per_kernel) == 3  # forward, dq, dk/dv
    dots = per_kernel[kernel]
    assert dots and len(dots) % products == 0
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype], eqn
        assert eqn.outvars[0].aval.dtype == jnp.float32, eqn


def test_gradients_with_padding():
    # seq=80 with block min(32, round_up(80,16))=32 pads to 96; padded
    # rows/cols must contribute zero gradient.
    q, k, v = _rand_qkv(jax.random.key(2), 1, 80, 1, 16)

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v) ** 2)
        return f

    flash = lambda q, k, v: flash_attention(q, k, v, False, 32, 32, True)
    ref = lambda q, k, v: local_attention(q, k, v)
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=3e-5, rtol=3e-4)


def test_bfloat16_inputs():
    q, k, v = _rand_qkv(jax.random.key(3), 1, 64, 2, 32, jnp.bfloat16)
    out = flash_attention(q, k, v, False, 64, 64, True)
    assert out.dtype == jnp.bfloat16
    ref = local_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def test_jit_compatible():
    q, k, v = _rand_qkv(jax.random.key(4), 1, 32, 1, 16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 32, 32, True))
    out = f(q, k, v)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
