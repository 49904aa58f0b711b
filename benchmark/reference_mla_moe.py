"""The served latent-attention expert model's weights from the seed, and its
plain reference.

Both are the benchmark's own: nothing here imports the program, and the
reference takes nothing that the program has made.  The weights are made on
the device in one jitted call from the seed, in bfloat16, the type they are
served in (norms' weights, the router and its bias float32), laid out as
``distkeras_tpu.models.LatentMoELM`` names its parameters (that layout is
the one thing taken from the program, because the weights are handed to it).

The reference is the architecture's forward pass in straightforward
``jax.numpy``, as the source's family publishes it (``sarvam_mla``,
DeepSeek-V2's lineage; what ``config.json`` does not state is listed under
``assumed`` in the configuration's file):

* layer: ``h = h + attn(norm(h)); h = h + ffn(norm(h))``, RMSNorm with a
  learned weight, every projection bias-free, a final norm and an untied
  head;
* latent attention in its *expanded* form over the whole context: ``q =
  norm(W_q x)`` per head, ``[c, k_r] = W_kva x``, ``c = norm(c)``, ``k_r =
  rope(k_r)``, ``k_n = W_uk c``, ``v = W_uv c``, scores ``(q_n . k_n +
  rope(q_r) . k_r) * d_q^-0.5 * m^2`` with ``m = 0.1 ln(factor) + 1``, a
  causal softmax, ``o = W_o concat_heads(P v)``; rotary positions by
  DeepSeek's YaRN on the rotary slice;
* the leading layer's feed-forward dense and gated; the others expert
  layers: ``s = sigmoid(W_r x)``, the top k of ``s + b`` (the bias picks,
  it does not weigh), weights ``s_i / sum_topk(s) * routed_scaling_factor``,
  expert ``W_down(silu(W_gate x) * W_up x)``, plus the shared expert.  Of the
  routed experts this chip holds ``held_experts = (first, count)``: **every
  held expert is applied to every token under its routing mask**, and what
  the absent experts would have added is left out, as in the program.

No cache, no paging, no latent absorbed into the query, no sorting of
assignments: one sequence a call, the served tokens teacher-forced.  In
float32 under ``jax.default_matmul_precision("highest")`` (the weights cast
to float32 a layer, and within an expert layer an expert, at a time, so that
no float32 copy of the experts stands beside the served weights) it is the
reference.  Computed with both operands of every product rounded to
``float8_e4m3fn`` and the rest in bfloat16 it is the control: the nearest
precision below the bfloat16 that the serving configuration states.
``bfloat16`` itself (the weights as they are served, bfloat16 activations)
can be read beside it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: heads whose scores over the whole context are held at a time
HEAD_GROUP = 8
#: the expert bias's standard deviation.  A trained bias balances the experts'
#: load; a random one unbalances it: at 0.1 (half a standard deviation of the
#: router's logits, through the sigmoid's slope) the fullest held expert took
#: 6.0 times the mean of a decode step (my chip run, PR 31), at 0.01 the load
#: is near what even routing gives
BIAS_SCALE = 0.01

#: the model's sizes, as ``make_weights`` (or ``configure``) was given them:
#: ``served_gaps`` gets the weights alone, and the weights' shapes do not
#: hold the rotary's constants, the scaling factor or which experts are held
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


def _shapes(m):
    """``{path: (shape, fan in)}``; fan in None: a norm's weight (ones); 0:
    the router's bias."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    rank, nope, rot = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                       m["qk_rope_head_dim"])
    held = (m.get("held_experts") or (0, m["num_experts"]))[1]
    wide = m["moe_intermediate_size"]
    shapes = {("embed",): ((m["vocab_size"], d), d), ("norm",): ((d,), None),
              ("head",): ((d, m["vocab_size"]), d)}
    for i in range(m["num_hidden_layers"]):
        layer = {"attn_norm": ((d,), None), "ffn_norm": ((d,), None),
                 "q": ((d, h, nope + rot), d), "q_norm": ((nope + rot,), None),
                 "kv_a": ((d, rank + rot), d), "kv_norm": ((rank,), None),
                 "k_up": ((rank, h, nope), rank),
                 "v_up": ((rank, h, m["v_head_dim"]), rank),
                 "o": ((h, m["v_head_dim"], d), h * m["v_head_dim"])}
        if i >= m["first_k_dense_replace"]:
            shared = wide * m["num_shared_experts"]
            layer.update({
                "router": ((d, m["num_experts"]), d),
                "router_bias": ((m["num_experts"],), 0),
                "experts_gate": ((held, d, wide), d),
                "experts_up": ((held, d, wide), d),
                "experts_down": ((held, wide, d), wide),
                "shared_gate": ((d, shared), d), "shared_up": ((d, shared), d),
                "shared_down": ((shared, d), shared)})
        else:
            wide0 = m["intermediate_size"]
            layer.update({"gate": ((d, wide0), d), "up": ((d, wide0), d),
                          "down": ((wide0, d), wide0)})
        shapes.update({("layers", i, name): entry
                       for name, entry in layer.items()})
    return shapes


def make_weights(seed, **model):
    """The whole parameter tree in one jitted call on the device: every
    matrix normal with standard deviation 1 / sqrt(fan in), in bfloat16 (made
    a leaf at a time: no float32 copy of the tree); the router float32; its
    bias normal at 0.01 (below), so that picking by ``s + b`` differs from weighing by
    ``s``; norms' weights one."""
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
            elif path[-1] == "router":
                leaf = fan ** -0.5 * jax.random.normal(k, shape, F32)
            else:
                leaf = (fan ** -0.5 * jax.random.normal(
                    k, shape, jnp.bfloat16)).astype(jnp.bfloat16)
            node = tree if len(path) == 1 else tree["layers"][path[1]]
            node[path[-1]] = leaf
        return tree

    return build(_key(seed))


# ---------------------------------------------------------------- the pieces


def _rms_norm(x, weight, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * weight.astype(F32))


def _inv_freq(dim, theta, scaling):
    """DeepSeek's YaRN: ``[dim / 2]`` frequencies, float64 on the host."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return extra
    original = scaling["original_max_position_embeddings"]
    turn = lambda n: (dim * math.log(original / (n * 2 * math.pi))
                      / (2 * math.log(theta)))
    low = max(math.floor(turn(scaling["beta_fast"])), 0)
    high = min(math.ceil(turn(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / scaling["factor"] * ramp + extra * (1 - ramp)


def _mscale(scaling, key):
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    return 0.1 * scaling[key] * math.log(scaling["factor"]) + 1.0


def _rope(x, angles):
    """``x [len, (heads,) dim]`` rotated by ``angles [len, dim / 2]``."""
    if x.ndim == 3:
        angles = angles[:, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(p, x, m, cast, operand):
    """``attn(norm(x))`` over the whole sequence ``x [len, dim]``."""
    length = x.shape[0]
    eps, nope = m["rms_norm_eps"], m["qk_nope_head_dim"]
    scaling = m.get("rope_scaling")
    mm = lambda spec, a, w: jnp.einsum(spec, operand(a), operand(cast(w)))
    h = _rms_norm(x, p["attn_norm"], eps).astype(x.dtype)
    q = _rms_norm(mm("ld,dhe->lhe", h, p["q"]), p["q_norm"], eps)
    kv = mm("ld,dw->lw", h, p["kv_a"]).astype(F32)
    c = _rms_norm(kv[:, :m["kv_lora_rank"]], p["kv_norm"], eps)
    amplitude = _mscale(scaling, "mscale") / _mscale(scaling, "mscale_all_dim")
    angles = (jnp.arange(length, dtype=F32)[:, None] * jnp.asarray(
        _inv_freq(m["qk_rope_head_dim"], m["rope_theta"], scaling), F32))
    q_r = _rope(q[..., nope:], angles) * amplitude
    k_r = _rope(kv[:, m["kv_lora_rank"]:], angles) * amplitude
    c = c.astype(x.dtype)
    k_n = mm("lc,chn->lhn", c, p["k_up"])
    v = mm("lc,chv->lhv", c, p["v_up"])
    scale = (nope + m["qk_rope_head_dim"]) ** -0.5 * _mscale(
        scaling, "mscale_all_dim") ** 2
    causal = jnp.tril(jnp.ones((length, length), bool))
    q_n, q_r, k_r = (t.astype(x.dtype) for t in (q[..., :nope], q_r, k_r))

    def heads(group):
        """A group of heads at a time: their scores over the whole context."""
        q_n, q_r, k_n, v = group
        scores = (jnp.einsum("qhn,khn->hqk", operand(q_n), operand(k_n))
                  + jnp.einsum("qhr,kr->hqk", operand(q_r), operand(k_r)))
        scores = jnp.where(causal[None], scores.astype(F32) * scale, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return jnp.einsum("hqk,khv->qhv", weights, operand(v))

    groups = m["num_attention_heads"] // min(HEAD_GROUP,
                                             m["num_attention_heads"])
    split = lambda t: jnp.moveaxis(
        t.reshape(length, groups, -1, t.shape[-1]), 1, 0)
    out = jax.lax.map(heads, tuple(split(t) for t in (q_n, q_r, k_n, v)))
    out = jnp.moveaxis(out, 0, 1).reshape(length, -1, v.shape[-1])
    return mm("qhv,hvd->qd", out.astype(x.dtype), p["o"]).astype(x.dtype)


def _gated(h, gate, up, down, cast, operand):
    mm = lambda a, w: operand(a) @ operand(cast(w))
    return mm((jax.nn.silu(mm(h, gate)) * mm(h, up)).astype(h.dtype), down)


def _feed_forward(p, x, m, cast, operand):
    """``ffn(norm(x))``: dense in a leading layer; else the held experts,
    each applied to every token and weighed by the routing's mask, plus the
    shared expert."""
    h = _rms_norm(x, p["ffn_norm"], m["rms_norm_eps"]).astype(x.dtype)
    if "router" not in p:
        return _gated(h, p["gate"], p["up"], p["down"], cast,
                      operand).astype(x.dtype)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(h.astype(F32) @ p["router"].astype(F32))
    _, ids = jax.lax.top_k(scores + p["router_bias"], m["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    weights = (picked / picked.sum(-1, keepdims=True)
               * m["routed_scaling_factor"])
    first, count = m.get("held_experts") or (0, m["num_experts"])
    # [tokens, held]: the weight with which each held expert enters a token
    mask = jnp.sum(jnp.where(
        ids[:, :, None] == first + jnp.arange(count)[None, None, :],
        weights[:, :, None], 0.0), axis=1)

    def expert(total, one):
        gate, up, down, weight = one
        y = _gated(h, gate, up, down, cast, operand)
        return total + weight[:, None] * y.astype(F32), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros(x.shape, F32),
        (p["experts_gate"], p["experts_up"], p["experts_down"], mask.T))
    shared = _gated(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                    cast, operand)
    return (routed + shared.astype(F32)).astype(x.dtype)


def _frozen(model):
    freeze = lambda v: (tuple(sorted(v.items())) if isinstance(v, dict)
                        else tuple(v) if isinstance(v, list) else v)
    return tuple(sorted((k, freeze(v)) for k, v in model.items()))


def _thawed(frozen):
    model = dict(frozen)
    if model.get("rope_scaling"):
        model["rope_scaling"] = dict(model["rope_scaling"])
    return model


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _layer(p, x, frozen, kind):
    """One layer, jitted alone: the float32 casts of its weights live only
    as long as it runs."""
    m = _thawed(frozen)
    cast, operand = _arithmetic(kind)
    x = x + _attention(p, x, m, cast, operand)
    return x + _feed_forward(p, x, m, cast, operand)


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _head(params, x, frozen, kind):
    m = _thawed(frozen)
    cast, operand = _arithmetic(kind)
    h = _rms_norm(x, params["norm"], m["rms_norm_eps"]).astype(x.dtype)
    return (operand(h) @ operand(cast(params["head"]))).astype(F32)


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


def forward(params, tokens, kind="float32"):
    """Logits ``[len, vocab]`` (float32) of one sequence ``tokens [len]``."""
    frozen = _frozen(_MODEL)
    stream = F32 if jnp.dtype(kind) == jnp.float32 else jnp.bfloat16
    x = params["embed"][tokens].astype(stream)
    for p in params["layers"]:
        x = _layer(p, x, frozen, kind)
    return _head(params, x, frozen, kind)


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
    for every request; the mask is causal and an expert layer works a token
    at a time, so padding behind changes nothing).  With ``control_dtype`` a
    second array comes back: the same reading for the tokens that the forward
    pass in that type puts first at those positions of the same sequence,
    the control."""
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
