"""The served shortcut-expert model's weights from the seed, and its plain
reference (``longcat_flash_omni``).

Both are the benchmark's own: nothing here imports the program, and the
reference takes nothing that the program has made.  The weights are made on
the device in one jitted call from the seed, in bfloat16, the type they are
served in (norms' weights, the router and its bias float32), laid out as
``distkeras_tpu.models.ShortcutMoELM`` names its parameters (that layout is
the one thing taken from the program, because the weights are handed to it):
a double layer is ``{"blocks": [sub-block 0, sub-block 1], "router",
"router_bias", "experts_gate", "experts_up", "experts_down"}``.

The reference is the architecture's forward pass in straightforward
``jax.numpy``, as the source's family publishes it (LongCat-Flash; what
``config.json`` does not state is listed under ``assumed`` in the
configuration's file).  For a residual stream ``x``, ``j`` in {0, 1}:

* ``MLA_j(h)``: ``c_q = norm(h W_qa)``, ``q = (c_q W_qb) [heads, 192] * s_q``
  with ``s_q = sqrt(hidden / q_lora_rank)``, ``[c, k_r] = h W_kva``, ``c =
  norm(c) * s_kv`` with ``s_kv = sqrt(hidden / kv_lora_rank)``, plain rotary
  on ``q_r`` and ``k_r`` (one key for all heads), ``k_n = c W_uk``, ``v = c
  W_uv``, scores ``(q_n . k_n + q_r . k_r) / sqrt(192)`` over the whole
  context, a causal softmax, out through ``W_o``;
* ``FFN_j(h) = (silu(h W_g) * (h W_u)) W_d``;
* ``MoE(u)``: ``s = softmax(u W_r)`` over the router's 768 outputs, the top
  12 of ``s + b`` (the bias picks, it does not weigh), ``w_e = 6 s_e`` (not
  normalised); the picked experts below ``n_routed_experts`` enter with
  ``w_e Expert_e(u)``, those at or above it are identity ("zero-compute")
  experts and enter with ``w_e u``.  Of the routed experts this chip holds
  ``held_experts = (first, count)``: **every held expert is applied to
  every token under its routing mask**, what the absent experts would have
  added is left out, as in the program, and the zero-compute term is added
  for every token (an identity expert has no weights and lies on no chip);
* the layer: ``x1 = x + MLA_0(norm(x)); u = norm(x1); x2 = x1 + FFN_0(u); x3
  = x2 + MLA_1(norm(x2)); x4 = x3 + FFN_1(norm(x3)); y = x4 + MoE(u)``: the
  expert branch reads the first sub-block's normed stream and rejoins at
  the layer's end.

No cache, no paging, no latent absorbed into the query, no sorting of
assignments: one sequence a call, the served tokens teacher-forced.  **In
blocks**, so that it fits beside 10.4 GB of served weights: every jitted
piece below casts the weights it is handed to float32 and lives no longer
than its call: an attention sub-block (0.36 GB of float32), ONE matrix of a
dense feed-forward (0.30 GB) and, inside the expert branch's scan, one held
expert at a time; never a layer, and never a layer's experts stacked.  In
float32 under ``jax.default_matmul_precision("highest")`` it is the
reference.  Computed with both operands of every product rounded to
``float8_e4m3fn`` and the rest in bfloat16 it is the control: the nearest
precision below the bfloat16 that the serving configuration states.
``bfloat16`` itself (the weights as they are served, bfloat16 activations)
can be read beside it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: heads whose scores over the whole context are held at a time
HEAD_GROUP = 8
#: the picking bias's standard deviation: small against the scores, which a
#: softmax over 768 outputs puts at the order of 1/768 = 1.3e-3 (a trained
#: bias balances the experts' load; a large random one unbalances it)
BIAS_SCALE = 1e-4

#: the model's sizes, as ``make_weights`` (or ``configure``) was given them:
#: ``served_gaps`` gets the weights alone, and their shapes do not hold the
#: rotary's base, the scaling factor or which experts are held
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
    """``{path: shape}`` of every leaf."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    rank, q_rank = m["kv_lora_rank"], m["q_lora_rank"]
    nope, rot = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    held = (m.get("held_experts") or (0, m["n_routed_experts"]))[1]
    wide, dense = m["expert_ffn_hidden_size"], m["ffn_hidden_size"]
    outputs = m["n_routed_experts"] + m["zero_expert_num"]
    block = {"attn_norm": (d,), "ffn_norm": (d,),
             "q_a": (d, q_rank), "q_a_norm": (q_rank,),
             "q_b": (q_rank, h, nope + rot),
             "kv_a": (d, rank + rot), "kv_norm": (rank,),
             "k_up": (rank, h, nope), "v_up": (rank, h, m["v_head_dim"]),
             "o": (h, m["v_head_dim"], d),
             "gate": (d, dense), "up": (d, dense), "down": (dense, d)}
    branch = {"router": (d, outputs), "router_bias": (outputs,),
              "experts_gate": (held, d, wide), "experts_up": (held, d, wide),
              "experts_down": (held, wide, d)}
    shapes = {("embed",): (m["vocab_size"], d), ("norm",): (d,),
              ("head",): (d, m["vocab_size"])}
    for i in range(m["num_layers"]):
        shapes.update({("layers", i, name): shape
                       for name, shape in branch.items()})
        shapes.update({("layers", i, "blocks", j, name): shape
                       for j in range(2) for name, shape in block.items()})
    return shapes


def make_weights(seed, **model):
    """The whole parameter tree in one jitted call on the device: every
    matrix normal at ONE standard deviation, ``1 / sqrt(hidden_size)``, in
    bfloat16 (made a leaf at a time: no float32 copy of the tree); the
    router float32; its bias normal at ``BIAS_SCALE``, so that picking by
    ``s + b`` differs from weighing by ``s``; norms' weights one.

    One deviation for every matrix, whatever its fan in, is what the two LoRA
    scales presuppose: under it a low-rank path's output is ``sqrt(rank /
    hidden)`` of a full-rank projection's and the scale ``sqrt(hidden /
    rank)`` brings it back, so that queries, keys and the attention's scores
    have unit variance.  Drawn at ``1 / sqrt(fan in)`` the paths are level
    already, the scales multiply the scores' deviation by 2 x 3.46, the
    softmaxes are all but one-hot and the network is chaotic: a bfloat16
    pass then puts another token than the float32 pass first at two thirds
    of the positions (my chip run, PR 33: the cell's first run read off the
    best at 65.5% of positions), where this draw reads a few per cent."""
    configure(**model)
    shapes = _shapes(model)
    deviation = model["hidden_size"] ** -0.5

    @jax.jit
    def build(key):
        tree = {"layers": [{"blocks": [{}, {}]}
                           for _ in range(model["num_layers"])]}
        for index, (path, shape) in enumerate(sorted(shapes.items(), key=str)):
            k = jax.random.fold_in(key, index)
            if path[-1] == "router_bias":
                leaf = BIAS_SCALE * jax.random.normal(k, shape, F32)
            elif len(shape) == 1:  # a norm's weight
                leaf = jnp.ones(shape, F32)
            elif path[-1] == "router":
                leaf = deviation * jax.random.normal(k, shape, F32)
            else:
                leaf = (deviation * jax.random.normal(
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


def _rope(x, angles):
    """``x [len, (heads,) dim]`` rotated by ``angles [len, dim / 2]``, the
    halves paired."""
    if x.ndim == 3:
        angles = angles[:, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


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


def _dense(h, b, kind):
    """``FFN_j(h)``, a matrix at a time."""
    hidden = _gate(_product(h, b["gate"], kind), _product(h, b["up"], kind))
    return _product(hidden, b["down"], kind)


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _attention(b, h, frozen, kind):
    """``MLA_j(h)`` over the whole sequence, ``h [len, dim]`` normed."""
    m = dict(frozen)
    cast, operand = _arithmetic(kind)
    length = h.shape[0]
    eps, nope = m["rms_norm_eps"], m["qk_nope_head_dim"]
    rank, rot = m["kv_lora_rank"], m["qk_rope_head_dim"]
    mm = lambda spec, a, w: jnp.einsum(spec, operand(a), operand(cast(w)))
    # the two LoRA scales: config.json holds the two flags as booleans; the
    # formula sqrt(hidden / rank) is the family's public modelling code's
    s_q = (m["hidden_size"] / m["q_lora_rank"]) ** 0.5 if m.get(
        "mla_scale_q_lora", True) else 1.0
    s_kv = (m["hidden_size"] / rank) ** 0.5 if m.get(
        "mla_scale_kv_lora", True) else 1.0
    c_q = _rms_norm(mm("ld,dq->lq", h, b["q_a"]), b["q_a_norm"], eps)
    q = mm("lq,qhe->lhe", c_q.astype(h.dtype), b["q_b"]).astype(F32) * s_q
    kv = mm("ld,dw->lw", h, b["kv_a"]).astype(F32)
    c = (_rms_norm(kv[:, :rank], b["kv_norm"], eps) * s_kv).astype(h.dtype)
    # plain rotary at rope_theta (config.json has no rope_scaling), the
    # halves paired: the checkpoint's interleaved layout is a permutation of
    # the random weights' columns
    inv_freq = m["rope_theta"] ** (
        -np.arange(0, rot, 2, dtype=np.float64) / rot)
    angles = (jnp.arange(length, dtype=F32)[:, None]
              * jnp.asarray(inv_freq, F32))
    q_r, k_r = _rope(q[..., nope:], angles), _rope(kv[:, rank:], angles)
    k_n = mm("lc,chn->lhn", c, b["k_up"])
    v = mm("lc,chv->lhv", c, b["v_up"])
    scale = (nope + rot) ** -0.5
    causal = jnp.tril(jnp.ones((length, length), bool))
    q_n, q_r, k_r = (t.astype(h.dtype) for t in (q[..., :nope], q_r, k_r))

    def heads(group):
        """A group of heads at a time: their scores over the whole context."""
        q_n, q_r, k_n, v = group
        scores = (jnp.einsum("qhn,khn->hqk", operand(q_n), operand(k_n))
                  + jnp.einsum("qhr,kr->hqk", operand(q_r), operand(k_r)))
        scores = jnp.where(causal[None], scores.astype(F32) * scale, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        return jnp.einsum("hqk,khv->qhv", weights, operand(v))

    groups = m["num_attention_heads"] // min(HEAD_GROUP,
                                             m["num_attention_heads"])
    split = lambda t: jnp.moveaxis(
        t.reshape(length, groups, -1, t.shape[-1]), 1, 0)
    out = jax.lax.map(heads, tuple(split(t) for t in (q_n, q_r, k_n, v)))
    out = jnp.moveaxis(out, 0, 1).reshape(length, -1, v.shape[-1])
    return mm("qhv,hvd->qd", out.astype(h.dtype), b["o"]).astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _expert_branch(p, u, frozen, kind):
    """``MoE(u)`` for ``u [tokens, dim]`` normed: the held experts, each
    applied to every token and weighed by the routing's mask (cast to
    float32 one expert at a time, inside the scan), plus the zero-compute
    experts' term."""
    m = dict(frozen)
    cast, operand = _arithmetic(kind)
    mm = lambda a, w: operand(a) @ operand(cast(w))
    routed = m["n_routed_experts"]
    with jax.default_matmul_precision("highest"):
        # no bias in the router's product; softmax over all 768 outputs
        scores = jax.nn.softmax(u.astype(F32) @ p["router"].astype(F32), -1)
    # the bias picks and does not weigh; the picked scores are scaled and
    # not normalised (config.json has no norm_topk_prob)
    _, ids = jax.lax.top_k(scores + p["router_bias"], m["moe_topk"])
    weights = (jnp.take_along_axis(scores, ids, axis=-1)
               * m["routed_scaling_factor"])
    first, count = m.get("held_experts") or (0, routed)
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
    # ids at or past n_routed_experts are identity experts: weight x input,
    # for every token (they lie on no chip: the token's own chip adds them)
    zero = jnp.sum(jnp.where(ids >= routed, weights, 0.0), -1, keepdims=True)
    return (held + zero * u.astype(F32)).astype(u.dtype)


def _layer(p, x, frozen, kind):
    """One double layer, piece by piece."""
    eps = dict(frozen)["rms_norm_eps"]
    first, second = p["blocks"]
    x = x + _attention(first, _normed(x, first["attn_norm"], eps),
                       frozen, kind)
    u = _normed(x, first["ffn_norm"], eps)
    branch = _expert_branch(p, u, frozen, kind)  # rejoins at the layer's end
    x = x + _dense(u, first, kind)
    x = x + _attention(second, _normed(x, second["attn_norm"], eps),
                       frozen, kind)
    x = x + _dense(_normed(x, second["ffn_norm"], eps), second, kind)
    return x + branch


def forward(params, tokens, kind="float32"):
    """Logits ``[len, vocab]`` (float32) of one sequence ``tokens [len]``."""
    frozen = _frozen(_MODEL)
    stream = F32 if jnp.dtype(kind) == jnp.float32 else jnp.bfloat16
    x = params["embed"][tokens].astype(stream)
    for p in params["layers"]:
        x = _layer(p, x, frozen, kind)
    h = _normed(x, params["norm"], _MODEL["rms_norm_eps"])
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
    for every request; the mask is causal and the feed-forwards and the
    expert branch work a token at a time, so padding behind changes
    nothing).  With ``control_dtype`` a second array comes back: the same
    reading for the tokens that the forward pass in that type puts first at
    those positions of the same sequence, the control."""
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
