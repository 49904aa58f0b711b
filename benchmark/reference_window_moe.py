"""The served sliding-window expert model's weights from the seed, and its
plain reference (``mimo_v2_5``).

Both are the benchmark's own: nothing here imports the program, and the
reference takes nothing that the program has made.  The weights are made on
the device in one jitted call from the seed, in bfloat16, the type they are
served in (norms' weights, the router, its bias and the sink logits
float32), laid out as ``distkeras_tpu.models.WindowMoELM`` names its
parameters (that layout is the one thing taken from the program, because
the weights are handed to it): a layer is ``{"attn_norm", "ffn_norm", "q",
"k", "v", "o"}``, a sliding layer with sinks adds ``"sink"``, a dense one
``"gate", "up", "down"`` and an expert one ``"router", "router_bias",
"experts_gate", "experts_up", "experts_down"``.

The reference is the architecture's forward pass in straightforward
``jax.numpy``, as the family's published config describes it (MiMo-V2-Flash;
what ``config.json`` does not state is listed under ``assumed`` in the
configuration's file).  For a residual stream ``x``:

* a layer: ``x = x + attn(norm(x)); x = x + ffn(norm(x))``; a final norm and
  an untied head; RMSNorm at ``layernorm_epsilon`` with a learned weight;
* attention of a layer whose ``hybrid_layer_pattern`` entry is 0 (global) or
  1 (sliding), each kind with its own head counts, widths and rotary base
  (``rope_theta``, ``swa_rope_theta``): ``q = h W_q``, ``k = h W_k``, ``v =
  h W_v``; the first ``int(head_dim x partial_rotary_factor)`` dimensions of
  ``q`` and ``k`` rotated, the halves paired, the rest as they are; every
  key-value head **repeated** for the ``heads / kv_heads`` consecutive query
  heads it serves; scores ``q . k / sqrt(head_dim)``; a global query at
  ``i`` sees the keys ``j <= i``, a sliding one ``i - window < j <= i``; a
  sliding layer's sink logit is one more column of the softmax, whose
  weight is dropped: ``p_j = exp(s_j) / (exp(sink) + sum_k exp(s_k))``; out
  ``W_o (attention_value_scale x P v)``;
* feed-forward: layer ``i`` with ``moe_layer_freq[i]`` 0 is a dense gated
  feed-forward, else the router ``s = sigmoid(h W_r)`` over all the
  ``n_routed_experts`` (float32), the top ``num_experts_per_tok`` of ``s +
  b`` (the bias picks, it does not weigh), weights ``s_i / sum_top s`` (the
  family's ``norm_topk_prob``) scaled by ``routed_scaling_factor`` (None:
  1); of the routed experts this chip holds ``held_experts = (first,
  count)``: **every held expert is applied to every token under its
  routing mask**, what the absent experts would add is left out, as in the
  program; no shared expert.

No cache, no paging, no ring, no sorting of assignments: one sequence a
call, the served tokens teacher-forced.  **In blocks**, so that it fits
beside 6.9 GB of served weights at 13,312 positions: every jitted piece
below casts the weights it is handed to float32 and lives no longer than its
call: an attention (a block of 512 queries and 8 query heads at a time,
against the whole context's keys in a global layer and against the block's
band, the ``window`` before it and its own, in a sliding one), ONE matrix
of a dense feed-forward and, inside the expert layer's scan, one held
expert at a time; never a layer, and never a layer's experts stacked.  In float32 under
``jax.default_matmul_precision("highest")`` it is the reference.  Computed
with both operands of every product rounded to ``float8_e4m3fn`` and the
rest in bfloat16 it is the control: the nearest precision below the
bfloat16 that the serving configuration states.  ``bfloat16`` itself (the
weights as they are served, bfloat16 activations) can be read beside it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: queries whose scores are held at a time
QUERY_BLOCK = 512
#: query heads whose scores are held at a time (a divisor of every head count)
HEAD_GROUP = 8
#: the picking bias's standard deviation: small against the sigmoid scores
BIAS_SCALE = 0.01

#: the model's sizes, as ``make_weights`` (or ``configure``) was given them:
#: ``served_gaps`` gets the weights alone, and their shapes do not hold the
#: rotary bases, the window or the layers' kinds
_MODEL = {}


def configure(**model):
    """Remember ``model.kwargs`` for ``served_gaps``."""
    _MODEL.clear()
    _MODEL.update(model)


def _key(seed):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                    impl="threefry2x32")


def _kind(m, index):
    """``(sliding, heads, kv_heads, head_dim, v_dim)`` of layer ``index``."""
    if m["hybrid_layer_pattern"][index] == 1:
        return (True, m["swa_num_attention_heads"],
                m["swa_num_key_value_heads"], m["swa_head_dim"],
                m["swa_v_head_dim"])
    return (False, m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["v_head_dim"])


def _shapes(m):
    """``{path: (shape, fan in)}`` of every leaf; fan None a norm's weight,
    0 the router's bias, 1 a sink logit."""
    d, vocab = m["hidden_size"], m["vocab_size"]
    held = (m.get("held_experts") or (0, m["n_routed_experts"]))[1]
    wide, dense = m["moe_intermediate_size"], m["intermediate_size"]
    shapes = {("embed",): ((vocab, d), d), ("norm",): ((d,), None),
              ("head",): ((d, vocab), d)}
    for i in range(m["num_hidden_layers"]):
        sliding, heads, kv_heads, head_dim, v_dim = _kind(m, i)
        layer = {"attn_norm": ((d,), None), "ffn_norm": ((d,), None),
                 "q": ((d, heads, head_dim), d),
                 "k": ((d, kv_heads, head_dim), d),
                 "v": ((d, kv_heads, v_dim), d),
                 "o": ((heads, v_dim, d), heads * v_dim)}
        if m.get("add_swa_attention_sink_bias", True) and sliding:
            layer["sink"] = ((heads,), 1)
        if m["moe_layer_freq"][i]:
            layer.update({
                "router": ((d, m["n_routed_experts"]), d),
                "router_bias": ((m["n_routed_experts"],), 0),
                "experts_gate": ((held, d, wide), d),
                "experts_up": ((held, d, wide), d),
                "experts_down": ((held, wide, d), wide)})
        else:
            layer.update({"gate": ((d, dense), d), "up": ((d, dense), d),
                          "down": ((dense, d), dense)})
        shapes.update({("layers", i, name): entry
                       for name, entry in layer.items()})
    return shapes


def make_weights(seed, **model):
    """The whole parameter tree in one jitted call on the device: every
    matrix normal at ``1 / sqrt(fan in)`` in bfloat16 (made a leaf at a time:
    no float32 copy of the tree); the router float32; its bias normal at
    ``BIAS_SCALE``, so that picking by ``s + b`` differs from weighing by
    ``s``; the sink logits normal at one, float32; norms' weights one."""
    configure(**model)
    shapes = _shapes(model)

    @jax.jit
    def build(key):
        tree = {"layers": [{} for _ in range(model["num_hidden_layers"])]}
        for index, (path, (shape, fan)) in enumerate(
                sorted(shapes.items(), key=str)):
            k = jax.random.fold_in(key, index)
            if fan is None:
                leaf = jnp.ones(shape, F32)
            elif fan == 0:
                leaf = BIAS_SCALE * jax.random.normal(k, shape, F32)
            elif path[-1] in ("router", "sink"):
                leaf = fan ** -0.5 * jax.random.normal(k, shape, F32)
            else:
                leaf = (fan ** -0.5 * jax.random.normal(
                    k, shape, jnp.bfloat16)).astype(jnp.bfloat16)
            node = tree
            for part in path[:-1]:
                node = node[part]
            node[path[-1]] = leaf
        return tree

    return build(_key(seed))


# ---------------------------------------------------------------- the pieces


def _arithmetic(kind):
    """``(cast of a weight, rounding of a product's operand)`` of a kind of
    forward pass: ``float32`` (the reference: weights cast to float32),
    ``bfloat16`` (the weights as served, bfloat16 activations) or an 8-bit
    float (both operands of every product rounded to it, the rest
    bfloat16; the softmax's weights stay bfloat16, since an 8-bit softmax
    flushes most of a long context's weights to nought)."""
    dtype = jnp.dtype(kind)
    if dtype == jnp.float32:
        return (lambda w: w.astype(F32)), (lambda t: t)
    if dtype.itemsize == 1:
        return (lambda w: w), (lambda t: t.astype(dtype).astype(jnp.bfloat16))
    return (lambda w: w.astype(dtype)), (lambda t: t.astype(dtype))


def _rms_norm(x, weight, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * weight.astype(F32))


def _rotate(x, positions, dims, theta):
    """The first ``dims`` of the last axis of ``x [len, heads, dim]``
    rotated by ``positions``, the halves paired; the rest as they are."""
    inv_freq = theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)
    angles = positions[:, None].astype(F32) * jnp.asarray(inv_freq, F32)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    half = dims // 2
    a, b = x[..., :half].astype(F32), x[..., half:dims].astype(F32)
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., dims:]], -1)


def _frozen(model):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, weight, eps):
    """``norm(x)`` in the stream's type."""
    return _rms_norm(x, weight, eps).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("kind",))
def _product(a, w, kind):
    """``a @ w`` for ONE matrix: its float32 cast lives as long as this
    call."""
    cast, operand = _arithmetic(kind)
    return (operand(a) @ operand(cast(w))).astype(a.dtype)


@jax.jit
def _gate(gate, up):
    return (jax.nn.silu(gate) * up).astype(gate.dtype)


def _dense(h, p, kind):
    """The dense gated feed-forward, a matrix at a time."""
    hidden = _gate(_product(h, p["gate"], kind), _product(h, p["up"], kind))
    return _product(hidden, p["down"], kind)


@functools.partial(jax.jit, static_argnames=("frozen", "kind", "index"))
def _attention(p, h, frozen, kind, index):
    """Layer ``index``'s attention over the whole sequence, ``h [len, dim]``
    normed."""
    m = dict(frozen)
    cast, operand = _arithmetic(kind)
    length = h.shape[0]
    sliding, heads, kv_heads, head_dim, v_dim = _kind(m, index)
    window = m["sliding_window"]
    dims = int(head_dim * m["partial_rotary_factor"])
    theta = m["swa_rope_theta"] if sliding else m["rope_theta"]
    mm = lambda spec, a, w: jnp.einsum(spec, operand(a), operand(cast(w)))
    positions = jnp.arange(length)
    q = _rotate(mm("ld,dhe->lhe", h, p["q"]).astype(F32), positions, dims,
                theta)
    k = _rotate(mm("ld,dhe->lhe", h, p["k"]).astype(F32), positions, dims,
                theta)
    v = mm("ld,dhe->lhe", h, p["v"]).astype(F32)
    # grouped-query attention as equations: each key-value head repeated for
    # the query heads it serves (query head i reads key-value head i // group)
    group = heads // kv_heads
    k, v = (jnp.repeat(t, group, axis=1).astype(h.dtype) for t in (k, v))
    q = q.astype(h.dtype)
    scale = head_dim ** -0.5
    together = min(HEAD_GROUP, heads)
    block = min(QUERY_BLOCK, length)
    blocks = -(-length // block)
    pad = blocks * block - length
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    sink = p["sink"].astype(F32) if "sink" in p else None
    if sliding:
        # the band: the window's keys before the block, then its own
        k = jnp.pad(k, ((window, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((window, pad), (0, 0), (0, 0)))
        span = block + window
    else:
        span = length

    def one(start):
        """The block of queries from ``start``: ``[block, heads, v_dim]``."""
        i = start + jnp.arange(block)[:, None]
        if sliding:
            j = start - window + jnp.arange(span)[None, :]
            keys = jax.lax.dynamic_slice_in_dim(k, start, span)
            values = jax.lax.dynamic_slice_in_dim(v, start, span)
            seen = (j <= i) & (j > i - window) & (j >= 0)
        else:
            j = jnp.arange(span)[None, :]
            keys, values = k, v
            seen = j <= i
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)

        def some(parts):
            """A group of heads' weighted values ``[group, block, v_dim]``."""
            qh, kh, vh, sh = parts
            scores = jnp.einsum("hqe,hke->hqk", operand(qh), operand(kh))
            scores = jnp.where(seen, scores.astype(F32) * scale, -jnp.inf)
            if sink is None:
                weights = jax.nn.softmax(scores, axis=-1)
            else:
                # the sink: one more column of the softmax, with no value
                column = jnp.broadcast_to(sh[:, None, None],
                                          scores.shape[:2] + (1,))
                weights = jax.nn.softmax(
                    jnp.concatenate([column, scores], -1), axis=-1)[..., 1:]
            return jnp.einsum("hqk,hkv->hqv", weights.astype(h.dtype),
                              operand(vh))

        split = lambda t: jnp.moveaxis(t, 1, 0).reshape(
            -1, together, *t.shape[:1], t.shape[-1])
        out = jax.lax.map(some, (
            split(qb), split(keys), split(values),
            (sink if sink is not None else jnp.zeros(heads, F32)).reshape(
                -1, together)))
        return out.reshape(heads, block, v_dim)

    out = jax.lax.map(one, jnp.arange(blocks) * block)  # [blocks, heads, ...]
    out = jnp.moveaxis(out, 1, 2).reshape(blocks * block, heads, v_dim)
    out = (out[:length].astype(F32) * m["attention_value_scale"]).astype(
        h.dtype)
    return mm("lhv,hvd->ld", out, p["o"]).astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _experts(p, u, frozen, kind):
    """An expert layer of ``u [tokens, dim]`` normed: the held experts, each
    applied to every token and weighed by the routing's mask (cast to
    float32 one expert at a time, inside the scan)."""
    m = dict(frozen)
    cast, operand = _arithmetic(kind)
    mm = lambda a, w: operand(a) @ operand(cast(w))
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(u.astype(F32) @ p["router"].astype(F32))
    _, ids = jax.lax.top_k(scores + p["router_bias"], m["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    scaling = m.get("routed_scaling_factor")
    weights = picked / jnp.sum(picked, -1, keepdims=True) * (
        1.0 if scaling is None else scaling)
    first, count = m.get("held_experts") or (0, m["n_routed_experts"])
    # [tokens, held]: the weight with which each held expert enters a token
    mask = jnp.sum(jnp.where(
        ids[:, :, None] == first + jnp.arange(count)[None, None, :],
        weights[:, :, None], 0.0), axis=1)

    def expert(total, one):
        gate, up, down, weight = one
        hidden = (jax.nn.silu(mm(u, gate)) * mm(u, up)).astype(u.dtype)
        return total + weight[:, None] * mm(hidden, down).astype(F32), None

    held, _ = jax.lax.scan(
        expert, jnp.zeros(u.shape, F32),
        (p["experts_gate"], p["experts_up"], p["experts_down"], mask.T))
    return held.astype(u.dtype)


def forward(params, tokens, kind="float32"):
    """Logits ``[len, vocab]`` (float32) of one sequence ``tokens [len]``."""
    frozen = _frozen(_MODEL)
    eps = _MODEL["layernorm_epsilon"]
    stream = F32 if jnp.dtype(kind) == jnp.float32 else jnp.bfloat16
    x = params["embed"][tokens].astype(stream)
    for index, p in enumerate(params["layers"]):
        x = x + _attention(p, _normed(x, p["attn_norm"], eps), frozen, kind,
                           index)
        h = _normed(x, p["ffn_norm"], eps)
        x = x + (_experts(p, h, frozen, kind) if "router" in p
                 else _dense(h, p, kind))
    h = _normed(x, params["norm"], eps)
    return _product(h, params["head"], kind).astype(F32)


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
    for every request; the mask is causal and the feed-forwards work a token
    at a time, so padding behind changes nothing).  With ``control_dtype`` a
    second array comes back: the same reading for the tokens that the
    forward pass in that type puts first at those positions of the same
    sequence, the control."""
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
