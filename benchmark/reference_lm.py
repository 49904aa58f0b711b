"""The served model's weights from the seed, and its plain reference.

Both are the benchmark's own: nothing here imports the program, and the
reference takes nothing that the program has made.  The weights are made on
the device in one jitted call from the seed, in float32, the type they are
served in, laid out as ``distkeras_tpu.models.TransformerLM`` names its
parameters (that layout is the one thing taken from the program, because the
weights are handed to it).

The reference is the architecture's forward pass in straightforward
``jax.numpy``: token plus position embedding, pre-LayerNorm blocks (attention
with a causal mask over the whole context, a GELU feed-forward of four times
the width), a final LayerNorm and an untied head.  No cache, no paging, no
batching of requests: one sequence a call, the served tokens teacher-forced.
In float32 under ``jax.default_matmul_precision("highest")`` it is the
reference.  Computed in bfloat16 (weights, activations, residual stream) it
is the control: the nearest precision below the float32 that the serving
configuration states.  An 8-bit float for the operands of every product, and
float32 at the backend's default precision, are read beside it
(``serve_probe.py control``; PERF.md section 2).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6  # the program's blocks use flax's default


def _key(seed):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                    impl="threefry2x32")


def _shapes(vocab_size, dim, heads, num_layers, max_len, mlp_ratio=4):
    """{path: (shape, scale)}; scale None marks a LayerNorm's scale (ones)."""
    head_dim = dim // heads
    fan = lambda n: n ** -0.5
    shapes = {("tok_embed", "embedding"): ((vocab_size, dim), fan(dim)),
              ("pos_embed", "embedding"): ((max_len, dim), fan(dim)),
              ("LayerNorm_0", "scale"): ((dim,), None),
              ("LayerNorm_0", "bias"): ((dim,), 0.02),
              ("lm_head", "kernel"): ((dim, vocab_size), fan(dim)),
              ("lm_head", "bias"): ((vocab_size,), 0.02)}
    for i in range(num_layers):
        block = f"block_{i}"
        attention = (block, "_SelfAttention_0")
        shapes.update({
            (block, "LayerNorm_0", "scale"): ((dim,), None),
            (block, "LayerNorm_0", "bias"): ((dim,), 0.02),
            (block, "LayerNorm_1", "scale"): ((dim,), None),
            (block, "LayerNorm_1", "bias"): ((dim,), 0.02),
            attention + ("qkv", "kernel"): ((dim, 3, heads, head_dim), fan(dim)),
            attention + ("qkv", "bias"): ((3, heads, head_dim), 0.02),
            attention + ("proj", "kernel"): ((heads, head_dim, dim), fan(dim)),
            attention + ("proj", "bias"): ((dim,), 0.02),
            (block, "Dense_0", "kernel"): ((dim, mlp_ratio * dim), fan(dim)),
            (block, "Dense_0", "bias"): ((mlp_ratio * dim,), 0.02),
            (block, "Dense_1", "kernel"): ((mlp_ratio * dim, dim),
                                           fan(mlp_ratio * dim)),
            (block, "Dense_1", "bias"): ((dim,), 0.02)})
    return shapes


def make_weights(seed, *, vocab_size, dim, heads, num_layers, max_len):
    """The whole parameter tree in one jitted call on the device: every
    matrix normal with standard deviation 1 / sqrt(fan in), every bias normal
    at 0.02 (no term of the forward pass is nought), LayerNorm scales one."""
    shapes = _shapes(vocab_size, dim, heads, num_layers, max_len)

    @jax.jit
    def build(key):
        tree = {}
        for index, (path, (shape, scale)) in enumerate(sorted(shapes.items())):
            if scale is None:
                leaf = jnp.ones(shape, jnp.float32)
            else:
                leaf = scale * jax.random.normal(
                    jax.random.fold_in(key, index), shape, jnp.float32)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf
        return tree

    return build(_key(seed))


def _layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def forward(params, tokens, operand=lambda t: t):
    """Logits ``[len, vocab]`` of one sequence ``tokens [len]``, in the type
    of ``params``.  ``operand`` is applied to both operands of every matrix
    product but the softmax's weights (the control rounds them there)."""
    length = tokens.shape[0]
    x = params["tok_embed"]["embedding"][tokens]
    x = x + params["pos_embed"]["embedding"][:length]
    causal = jnp.tril(jnp.ones((length, length), bool))
    layers = sum(1 for name in params if name.startswith("block_"))
    dense = lambda h, p: operand(h) @ operand(p["kernel"]) + p["bias"]
    for i in range(layers):
        p = params[f"block_{i}"]
        a = p["_SelfAttention_0"]
        h = _layer_norm(x, p["LayerNorm_0"])
        qkv = jnp.einsum("ld,dkhe->lkhe", operand(h),
                         operand(a["qkv"]["kernel"])) + a["qkv"]["bias"]
        q, k, v = operand(qkv[:, 0]), operand(qkv[:, 1]), operand(qkv[:, 2])
        scores = jnp.einsum("qhe,khe->hqk", q, k) / np.sqrt(q.shape[-1])
        scores = jnp.where(causal[None], scores.astype(jnp.float32), -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("hqk,khe->qhe", weights, v)
        x = x + jnp.einsum("qhe,hed->qd", operand(out),
                           operand(a["proj"]["kernel"])) + a["proj"]["bias"]
        h = jax.nn.gelu(dense(_layer_norm(x, p["LayerNorm_1"]), p["Dense_0"]),
                        approximate=True)
        x = x + dense(h, p["Dense_1"])
    return dense(_layer_norm(x, params["LayerNorm_0"]), params["lm_head"])


@jax.jit
def _reference_logits(params, tokens):
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens).astype(jnp.float32)


@jax.jit
def _gaps(logits, served):
    """How far each served token's logit lies below the row's best."""
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]


@functools.partial(jax.jit, static_argnames=("dtype",))
def _low_precision_choice(params, tokens, dtype):
    """The token that the forward pass in a lower precision puts first at
    each position.  ``bfloat16`` (the control): weights, activations and
    products in that type.  ``float32``: the reference's own weights,
    products at the backend's default precision (on the TPU one bfloat16
    pass, as the program's own products).  An 8-bit float (``float8_e4m3fn``): both operands of every matrix
    product (weights, activations, keys and values) rounded to it, the rest
    in bfloat16; the softmax's weights stay in bfloat16, since an 8-bit
    softmax flushes most of a long context's weights to nought."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return jnp.argmax(forward(params, tokens), axis=-1).astype(jnp.int32)
    if dtype.itemsize == 1:
        cast = jax.tree.map(lambda leaf: leaf.astype(jnp.bfloat16), params)
        operand = lambda t: t.astype(dtype).astype(jnp.bfloat16)
        return jnp.argmax(forward(cast, tokens, operand),
                          axis=-1).astype(jnp.int32)
    cast = jax.tree.map(lambda leaf: leaf.astype(dtype), params)
    return jnp.argmax(forward(cast, tokens), axis=-1).astype(jnp.int32)


def served_gaps(params, prompt, served, width, control_dtype=None):
    """For one finished request: at each generated position the gap by which
    the served token's reference logit lies below the reference's best
    (``[len(served)]``, float32 on the host).  The sequence is the prompt
    with the served tokens behind it, padded to ``width`` (one compiled shape
    for every request; the mask is causal, so padding behind changes
    nothing).  With ``control_dtype`` a second array comes back: the same
    reading for the tokens that the forward pass in that type puts first at
    those positions of the same sequence, the control."""
    prompt, served = list(prompt), list(served)
    sequence = np.zeros(width, np.int32)
    fed = (prompt + served)[:-1]  # the last served token is never fed back
    sequence[:len(fed)] = fed
    rows = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
    tokens = jnp.asarray(sequence)
    logits = _reference_logits(params, tokens)
    target = np.zeros(width, np.int32)
    target[rows] = served
    gaps = np.asarray(_gaps(logits, jnp.asarray(target)))[rows]
    if control_dtype is None:
        return gaps
    choice = _low_precision_choice(params, tokens, str(control_dtype))
    return gaps, np.asarray(_gaps(logits, choice))[rows]
