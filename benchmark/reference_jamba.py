"""The served Jamba model's weights from the seed, and its plain reference
(``jamba2_3b``).

Both are the benchmark's own: nothing here imports the program, and the
reference takes nothing that the program has made.  The weights are made on
the device in one jitted call from the seed, in bfloat16, the type they are
served in (norms' weights, the convolution's and the step's biases,
``A_log`` and ``D`` float32), laid out as ``distkeras_tpu.models.JambaLM``
names its parameters (that layout is the one thing taken from the program,
because the weights are handed to it): ``{"embed", "norm", "layers"}``, every
layer ``{"input_norm", "ffn_norm", "gate", "up", "down"}`` and either an
attention layer's ``{"q", "k", "v", "o"}`` or a Mamba layer's ``{"in_proj",
"conv_w", "conv_b", "x_proj", "dt_norm", "b_norm", "c_norm", "dt_proj",
"dt_bias", "A_log", "D", "out_proj"}``.  ``A_log``, ``dt_bias`` and ``D``
follow Mamba's own initialisation (``log(1 .. d_state)`` a channel, the
inverse softplus of steps log-uniform in [0.001, 0.1], ones): decays that a
trained model has, so that the comparison tests the recurrence.

The reference is the architecture's forward pass in straightforward
``jax.numpy``, as the family's published config describes it (AI21-Jamba2-3B,
``model_type`` ``jamba``; what ``config.json`` does not state is listed under
``assumed`` in the configuration's file).  For a residual stream ``x``:

* layer ``i`` is attention iff ``i % attn_layer_period == attn_layer_offset``
  (HF ``JambaConfig``'s rule), else Mamba; ``x = x + mixer(norm(x)); x = x +
  ffn(norm(x))``; a final norm and the head the embedding transposed;
  RMSNorm at ``rms_norm_eps`` with a learned weight;
* attention: ``q = h W_q``, ``k = h W_k``, ``v = h W_v``, no bias, **no
  position at all**; every key-value head **repeated** for the query heads it
  serves; causal softmax of ``q . k / sqrt(head_dim)``; out ``W_o P v``;
* Mamba: ``[x; z] = h W_in``; a depthwise causal convolution of
  ``mamba_d_conv`` taps with its bias over ``x`` (zeros before position 0),
  then ``silu``; ``[delta; B; C] = x~ W_x``, each through its own RMSNorm
  (Jamba's three inner norms); ``Delta = softplus(delta W_dt + b_dt)``; ``A =
  -exp(A_log)``; the selective scan as a **sequential** ``lax.scan`` over the
  positions, ``h_t = exp(Delta_t A) h_{t-1} + Delta_t B_t x~_t``, ``y_t = C_t
  . h_t + D x~_t``; out ``(y * silu(z)) W_out``;
* feed-forward: ``(silu(h W_gate) * h W_up) W_down``.

No cache, no paging, no recurrent store, no kernel: one sequence a call, the
served tokens teacher-forced, the whole context at once.  **A layer at a
time**, so that it fits beside 6.1 GB of served weights: every jitted piece
below casts the weights it is handed to float32 and lives no longer than its
call (a mixer, or one matrix of a feed-forward or of the head).  In float32
under ``jax.default_matmul_precision("highest")`` it is the reference.
Computed with both operands of every product rounded to ``float8_e4m3fn``
and the rest in bfloat16 (the scan's state among it) it is the control: the
nearest precision below the bfloat16 that the serving configuration states.
``bfloat16`` itself (the weights as they are served, bfloat16 activations
and state) can be read beside it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: queries whose scores are held at a time
QUERY_BLOCK = 512
#: the scale of the convolution's bias
BIAS_SCALE = 0.02
#: the steps of Mamba's initialisation, ``(dt_min, dt_max)``
DT_RANGE = (0.001, 0.1)

#: the model's sizes, as ``make_weights`` (or ``configure``) was given them:
#: ``served_gaps`` gets the weights alone, and their shapes do not hold the
#: layers' kinds or the norms' epsilon
_MODEL = {}


def configure(**model):
    """Remember ``model.kwargs`` for ``served_gaps``."""
    _MODEL.clear()
    _MODEL.update(model)


def _key(seed):
    """A key from any whole number (seeds may pass 2**31)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                    impl="threefry2x32")


def _attention_layer(m, index):
    return index % m["attn_layer_period"] == m["attn_layer_offset"]


def _shapes(m):
    """``{path: (shape, fan in)}`` of every leaf; fan None a norm's weight or
    ``D`` (ones), a string one of Mamba's own vectors."""
    d, vocab = m["hidden_size"], m["vocab_size"]
    di = m["mamba_expand"] * d
    n, r, taps = m["mamba_d_state"], m["mamba_dt_rank"], m["mamba_d_conv"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd, ff = d // heads, m["intermediate_size"]
    shapes = {("embed",): ((vocab, d), d), ("norm",): ((d,), None)}
    for i in range(m["num_hidden_layers"]):
        layer = {"input_norm": ((d,), None), "ffn_norm": ((d,), None),
                 "gate": ((d, ff), d), "up": ((d, ff), d),
                 "down": ((ff, d), ff)}
        if _attention_layer(m, i):
            layer.update({"q": ((d, heads, hd), d), "k": ((d, kv, hd), d),
                          "v": ((d, kv, hd), d),
                          "o": ((heads, hd, d), heads * hd)})
        else:
            layer.update({
                "in_proj": ((d, 2 * di), d), "conv_w": ((taps, di), taps),
                "conv_b": ((di,), "bias"), "x_proj": ((di, r + 2 * n), di),
                "dt_norm": ((r,), None), "b_norm": ((n,), None),
                "c_norm": ((n,), None), "dt_proj": ((r, di), r),
                "dt_bias": ((di,), "dt_bias"), "A_log": ((n, di), "A_log"),
                "D": ((di,), None), "out_proj": ((di, d), di)})
        shapes.update({("layers", i, name): entry
                       for name, entry in layer.items()})
    return shapes


def _leaf(key, shape, fan):
    if fan is None:
        return jnp.ones(shape, F32)
    if fan == "bias":
        return BIAS_SCALE * jax.random.normal(key, shape, F32)
    if fan == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))[:, None], shape)
    if fan == "dt_bias":
        low, high = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
        dt = jnp.exp(jax.random.uniform(key, shape, F32) * (high - low) + low)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt
    return (fan ** -0.5 * jax.random.normal(key, shape, jnp.bfloat16)).astype(
        jnp.bfloat16)


def make_weights(seed, **model):
    """The whole parameter tree in one jitted call on the device: every
    matrix normal at ``1 / sqrt(fan in)`` in bfloat16 (made a leaf at a time:
    no float32 copy of the tree), the convolution's bias normal at
    ``BIAS_SCALE``, ``dt_bias``, ``A_log`` and ``D`` by Mamba's own rule,
    norms' weights one, all vectors float32."""
    configure(**model)
    shapes = _shapes(model)

    @jax.jit
    def build(key):
        tree = {"layers": [{} for _ in range(model["num_hidden_layers"])]}
        for index, (path, (shape, fan)) in enumerate(
                sorted(shapes.items(), key=str)):
            node = tree
            for part in path[:-1]:
                node = node[part]
            node[path[-1]] = _leaf(jax.random.fold_in(key, index), shape, fan)
        return tree

    return build(_key(seed))


# ---------------------------------------------------------------- the pieces


def _arithmetic(kind):
    """``(cast of a weight, rounding of a product's operand, the stream's
    type)`` of a kind of forward pass: ``float32`` (the reference: weights
    cast to float32), ``bfloat16`` (the weights as served, bfloat16
    activations) or an 8-bit float (both operands of every product rounded
    to it, the rest bfloat16)."""
    dtype = jnp.dtype(kind)
    if dtype == jnp.float32:
        return (lambda w: w.astype(F32)), (lambda t: t), F32
    if dtype.itemsize == 1:
        return ((lambda w: w),
                (lambda t: t.astype(dtype).astype(jnp.bfloat16)),
                jnp.bfloat16)
    return (lambda w: w.astype(dtype)), (lambda t: t.astype(dtype)), dtype


def _rms_norm(x, weight, eps):
    """RMSNorm in float32, back in ``x``'s type."""
    x32 = x.astype(F32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            * weight.astype(F32)).astype(x.dtype)


def _frozen(model):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, weight, eps):
    return _rms_norm(x, weight, eps)


@functools.partial(jax.jit, static_argnames=("kind", "spec"))
def _product(a, w, kind, spec="ld,dv->lv"):
    """``einsum(spec, a, w)`` for ONE matrix: its float32 cast lives as long
    as this call."""
    cast, operand, _ = _arithmetic(kind)
    return jnp.einsum(spec, operand(a), operand(cast(w))).astype(a.dtype)


@jax.jit
def _gate(gate, up):
    return (jax.nn.silu(gate) * up).astype(gate.dtype)


def _dense(h, p, kind):
    """The gated feed-forward, a matrix at a time."""
    hidden = _gate(_product(h, p["gate"], kind), _product(h, p["up"], kind))
    return _product(hidden, p["down"], kind)


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _attention(p, h, frozen, kind):
    """An attention layer over the whole sequence, ``h [len, dim]`` normed:
    no position, the key-value heads repeated, causal, a block of queries
    at a time."""
    m = dict(frozen)
    cast, operand, _ = _arithmetic(kind)
    mm = lambda spec, a, w: jnp.einsum(spec, operand(a), operand(cast(w)))
    length = h.shape[0]
    heads = m["num_attention_heads"]
    head_dim = m["hidden_size"] // heads
    group = heads // m["num_key_value_heads"]
    q = mm("ld,dhe->lhe", h, p["q"]).astype(h.dtype)
    k = jnp.repeat(mm("ld,dhe->lhe", h, p["k"]), group, axis=1).astype(h.dtype)
    v = jnp.repeat(mm("ld,dhe->lhe", h, p["v"]), group, axis=1).astype(h.dtype)
    block = min(QUERY_BLOCK, length)
    blocks = -(-length // block)
    q = jnp.pad(q, ((0, blocks * block - length), (0, 0), (0, 0)))

    def one(start):
        """The block of queries from ``start``: ``[block, heads, head_dim]``."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qhe,khe->hqk", operand(qb), operand(k))
        seen = (jnp.arange(length)[None, :]
                <= start + jnp.arange(block)[:, None])
        scores = jnp.where(seen, scores.astype(F32) * head_dim ** -0.5,
                           -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        return jnp.einsum("hqk,khe->qhe", weights, operand(v))

    out = jax.lax.map(one, jnp.arange(blocks) * block)
    out = out.reshape(blocks * block, heads, head_dim)[:length].astype(h.dtype)
    return mm("lhe,hed->ld", out, p["o"]).astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _mamba(p, h, frozen, kind):
    """A Mamba mixer over the whole sequence, ``h [len, dim]`` normed: the
    convolution, the three norms and the selective scan as a sequential
    ``lax.scan`` over the positions."""
    m = dict(frozen)
    cast, operand, stream = _arithmetic(kind)
    mm = lambda a, w: (operand(a) @ operand(cast(w))).astype(stream)
    eps = m["rms_norm_eps"]
    di = m["mamba_expand"] * m["hidden_size"]
    n, r, taps = m["mamba_d_state"], m["mamba_dt_rank"], m["mamba_d_conv"]
    xz = mm(h, p["in_proj"])
    x, z = xz[:, :di], xz[:, di:]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    length = x.shape[0]
    conv = p["conv_b"].astype(stream) + sum(
        p["conv_w"][k].astype(stream) * padded[k:k + length]
        for k in range(taps))
    x = jax.nn.silu(conv)
    dbc = mm(x, p["x_proj"])
    delta = _rms_norm(dbc[:, :r], p["dt_norm"], eps)
    b = _rms_norm(dbc[:, r:r + n], p["b_norm"], eps)
    c = _rms_norm(dbc[:, r + n:], p["c_norm"], eps)
    delta = jax.nn.softplus(mm(delta, p["dt_proj"])
                            + p["dt_bias"].astype(stream))
    a = -jnp.exp(p["A_log"].astype(stream))  # [n, di]

    def position(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t[None, :] * a) * state
                 + b_t[:, None] * (dt_t * x_t)[None, :])
        return state, jnp.sum(c_t[:, None] * state, axis=0)

    _, y = jax.lax.scan(position, jnp.zeros((n, di), stream),
                        (x, delta, b, c))
    y = y + p["D"].astype(stream) * x
    return mm(y * jax.nn.silu(z), p["out_proj"])


def forward(params, tokens, kind="float32"):
    """Logits ``[len, vocab]`` (float32) of one sequence ``tokens [len]``."""
    frozen = _frozen(_MODEL)
    eps = _MODEL["rms_norm_eps"]
    stream = _arithmetic(kind)[2]
    x = params["embed"][tokens].astype(stream)
    for index, p in enumerate(params["layers"]):
        h = _normed(x, p["input_norm"], eps)
        mixer = (_attention if _attention_layer(_MODEL, index) else _mamba)
        x = x + mixer(p, h, frozen, kind)
        x = x + _dense(_normed(x, p["ffn_norm"], eps), p, kind)
    h = _normed(x, params["norm"], eps)
    return _product(h, params["embed"], kind, "ld,vd->lv").astype(F32)


def _reference_logits(params, tokens):
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens)


@jax.jit
def _gaps(logits, served):
    """How far each served token's logit lies below the row's best."""
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]


def served_gaps(params, prompt, served, width, control_dtype=None):
    """For one finished request: at each generated position the gap by which
    the served token's reference logit lies below the reference's best
    (``[len(served)]``, float32 on the host).  The sequence is the prompt
    with the served tokens behind it, padded to ``width`` (one compiled shape
    for every request; every mixer is causal, so padding behind changes
    nothing before it).  With ``control_dtype`` a second array comes back:
    the same reading for the tokens that the forward pass in that type puts
    first at those positions of the same sequence, the control."""
    if not _MODEL:
        raise RuntimeError("make_weights (or configure) has to come first: "
                           "the weights' shapes do not hold the model's sizes")
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
    choice = jnp.argmax(forward(params, tokens, kind=str(control_dtype)),
                        axis=-1).astype(jnp.int32)
    return gaps, np.asarray(_gaps(logits, choice))[rows]
