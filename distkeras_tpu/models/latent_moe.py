"""A causal LM of latent-attention (MLA) layers over sparse expert layers.

The block of today's large open sparse models (DeepSeek-V2's lineage; the
shapes of ``sarvam_mla``): RMSNorm, bias-free projections, rotary positions
on a slice of each head (DeepSeek's YaRN), multi-head **latent** attention
that caches one compressed row a position for all heads, one leading dense
gated feed-forward layer, then **expert layers**: a sigmoid router over all
the experts with a bias that picks and does not weigh, the top-k's weights
normalised and scaled, a shared expert on every token.

Equations (``h`` a layer's input, ``norm`` RMSNorm with a learned weight):

* layer: ``h = h + attn(norm(h)); h = h + ffn(norm(h))``; a final ``norm``
  and an untied head;
* attention: ``q = norm_q(W_q x)`` per head (``qk_nope + qk_rope`` wide),
  ``[c, k_r] = W_kva x``, ``c = norm_kv(c)``, ``k_r = rope(k_r)`` (one for
  all heads), ``k_n = W_uk c``, ``v = W_uv c``, ``q = [q_n, rope(q_r)]``,
  scores ``(q_n . k_n + q_r . k_r) * scale``, causal softmax in float32,
  ``o = W_o concat_heads(P v)``;
* expert layer: ``s = sigmoid(W_r x)`` in float32, the top k of ``s + b``,
  weights ``s_i / sum_topk(s) * routed_scaling_factor``, expert
  ``W_down(silu(W_gate x) * W_up x)``, plus the shared expert.

**The chip's share.**  ``held_experts = (first, count)`` tells an expert
layer which of the ``num_experts`` routed experts it holds (expert
parallelism's share of a layer).  It routes over all of them, computes the
held experts' terms for the tokens routed to them and **drops nothing**:
the assignments are sorted by expert, those to absent experts past the last
group, and the grouped product **walks row tiles**: each held group's
sorted rows are cut into tiles, so a tile belongs to one expert, whose three
matrices are read for it and only for it (a group's last tile is filled up
with rows that go nowhere); a loop as long as the tiles that hold a row
gathers a tile's rows, computes the expert's term and adds it, weighed, into
the tokens' rows.  A touched expert's weights are read once a call where its
rows fit one tile, and the tile's height follows the traced row count
(:func:`tile_height`).  What absent experts would have added is left out;
nothing stands in for the other chips or their exchange.

**Two attention paths** (both write the same ``[c, k_r]`` row):

* *expanded*, for a whole sequence or a prefill chunk: per-head keys and
  values are made from the chunk's latents and attended densely, a block of
  queries at a time;
* *absorbed*, for the serving step
  (:func:`~distkeras_tpu.serving.cache.paged_latent_attention`): the query
  is carried into the latent's space (``q_n W_uk``), scored against the
  cached rows as they are, and the weighted latent is carried out through
  ``W_uv``: nothing per head is cached or expanded.

Precision: products take their operands in the weights' type (bfloat16 when
served so) and accumulate in float32; the residual stream, norms, router
scores, softmax and the running attention state are float32.

A plain dataclass, not a flax module: ``init(key)`` makes a parameter tree,
``model(params, tokens)`` is the full forward (no cache), and
``decode_spec(params)`` is what :class:`~distkeras_tpu.serving.ServingEngine`
serves it by.  The tensor-parallel (``mesh=``) and speculative
(``draft_model=``) builds are not supported for this block yet.

**Shared with the other latent-attention block** (``models/scmoe.py``'s
``ShortcutMoELM``, which imports them from here): the module-level
:func:`rms_norm`, :func:`rope`, :func:`expanded_attention`,
:func:`absorbed_step`, :func:`gated`, :func:`held_experts_terms` with
:func:`tile_height`, :func:`embed_tokens`, :func:`final_head`,
:func:`geometry_of`, :func:`init_params`, :func:`moe_instruments` and
:func:`observe_walk`.  They take what differs
between the blocks (the softmax's scale, the latent's width, the held range,
the router's width) as arguments; ``LatentMoELM``'s methods of the same
names hand them its own.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["LatentMoELM"]

F32 = jnp.float32
#: queries of a chunk that the expanded attention scores at a time
QUERY_BLOCK = 512


def tile_height(rows, experts):
    """Rows of one tile of the experts' walk, from the traced shape alone:
    the power of two at or under twice the mean group (``rows / experts``
    under an even router), so that nearly every group fits one tile and its
    expert's weights are read once; at least 16 (one packed bfloat16 tile of
    the chip), at most 256 (beyond it the padding's products cost more than
    a second read: PERF.md, PR 32)."""
    return min(max(16, 1 << max((2 * rows // experts).bit_length() - 1, 0)),
               256)


def _dot(x, w, spec):
    """``einsum(spec, x, w)``: operands in the weights' type, float32 out."""
    return jnp.einsum(spec, x.astype(w.dtype), w, preferred_element_type=F32)


def rms_norm(x, weight, eps):
    x = x.astype(F32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * scale * weight.astype(F32)


def yarn_inv_freq(dim, theta, scaling):
    """Rotary frequencies ``[dim / 2]`` under DeepSeek's YaRN: dimensions
    that turn more than ``beta_fast`` times over the original context keep
    their frequency, those under ``beta_slow`` turns are interpolated by
    ``factor``, with a linear ramp between.  ``scaling`` None: plain."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return extra.astype(np.float32)
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def correction_dim(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(scaling, key):
    """``0.1 * scaling[key] * ln(factor) + 1`` (1 without scaling)."""
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    return 0.1 * float(scaling[key]) * math.log(scaling["factor"]) + 1.0


def rope(x, positions, inv_freq, amplitude=1.0):
    """Rotate the last axis of ``x [..., rows, (heads,) dim]`` by
    ``positions [..., rows]``: halves paired (``rotate_half``)."""
    angles = positions[..., None].astype(F32) * inv_freq
    if x.ndim == angles.ndim + 1:
        angles = angles[..., None, :]  # one rotation for all heads
    cos, sin = jnp.cos(angles) * amplitude, jnp.sin(angles) * amplitude
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def init_params(tree, shapes, key, dtype, bias_scale):
    """Fill ``tree`` (the containers, empty) with a leaf for every ``path:
    (shape, fan)`` of ``shapes``: matrices normal at ``1 / sqrt(fan)`` in
    ``dtype`` (``fan`` is the block's to say: a matrix's fan in, or one
    number for every matrix), ``fan`` None a norm's weight (ones, float32),
    0 a router's bias (normal at ``bias_scale``, float32); a leaf named
    ``router`` stays float32."""
    for index, (path, (shape, fan)) in enumerate(
            sorted(shapes.items(), key=str)):
        if fan is None:
            leaf = jnp.ones(shape, F32)
        else:
            noise = jax.random.normal(jax.random.fold_in(key, index),
                                      shape, F32)
            leaf = (bias_scale * noise if fan == 0
                    else (fan ** -0.5 * noise).astype(
                        F32 if path[-1] == "router" else dtype))
        node = tree
        for part in path[:-1]:
            node = node[part]
        node[path[-1]] = leaf
    return tree


def expanded_attention(p, q_n, q_r, c, k_r, scale):
    """Causal latent attention of a chunk that starts at position 0, in the
    expanded form: per-head keys and values from the chunk's own latents
    (``p["k_up"]``, ``p["v_up"]``), a block of queries at a time against the
    keys up to the block's end, out through ``p["o"]``.  ``[batch, rows,
    dim]``."""
    rows = c.shape[1]
    kind = p["k_up"].dtype
    k_n = _dot(c, p["k_up"], "brc,chn->brhn")
    v = _dot(c, p["v_up"], "brc,chv->brhv").astype(kind)
    q = jnp.concatenate([q_n, q_r], axis=-1).astype(kind)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, :, None, :],
                               k_n.shape[:-1] + k_r.shape[-1:])],
        axis=-1).astype(kind)
    out = []
    for start in range(0, rows, QUERY_BLOCK):
        end = min(rows, start + QUERY_BLOCK)
        s = jnp.einsum("bqhe,bkhe->bhqk", q[:, start:end], k[:, :end],
                       preferred_element_type=F32) * scale
        causal = (jnp.arange(end)[None, :]
                  <= jnp.arange(start, end)[:, None])
        weights = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhv->bqhv", weights.astype(kind),
                              v[:, :end], preferred_element_type=F32))
    return _dot(jnp.concatenate(out, axis=1), p["o"], "bqhv,hvd->bqd")


def absorbed_step(p, pool, tables, pos, q_n, q_r, c, k_r, latent_width,
                  scale):
    """The serving step's latent attention, one token a slot (``rows`` 1),
    in the absorbed form over one pool's paged rows: ``(pool, [slots, 1,
    dim])``."""
    from distkeras_tpu.serving.cache import paged_latent_attention

    q_c = _dot(q_n[:, 0], p["k_up"], "shn,chn->shc")
    row = jnp.concatenate([c, k_r], axis=-1)[:, 0].astype(pool.dtype)
    pool, o_c = paged_latent_attention(
        pool, tables, pos, jnp.concatenate([q_c, q_r[:, 0]], axis=-1),
        row, latent_width, scale)
    o = _dot(o_c, p["v_up"], "shc,chv->shv")
    return pool, _dot(o, p["o"], "shv,hvd->sd")[:, None]


def gated(x, gate, up, down):
    """The gated feed-forward ``(silu(x gate) * (x up)) down``."""
    return _dot(jax.nn.silu(_dot(x, gate, "td,dw->tw"))
                * _dot(x, up, "td,dw->tw"), down, "tw,wd->td")


def embed_tokens(params, tokens, positions):
    """``DecodeSpec.embed`` of a block without a position table: the
    tokens' rows in float32."""
    return params["embed"][tokens].astype(F32)


def geometry_of(model):
    """``DecodeSpec.geometry`` of a dataclass model: every field, as text."""
    return tuple(sorted((f.name, str(getattr(model, f.name)))
                        for f in dataclasses.fields(model)))


def final_head(params, x, eps, at=None):
    """The final norm and the untied head over ``x [batch, rows, dim]``;
    with ``at`` one row of a ``[1, width, dim]`` chunk alone: the head runs
    once."""
    if at is not None:
        x = jax.lax.dynamic_index_in_dim(x[0], at, axis=0, keepdims=False)
    return _dot(rms_norm(x, params["norm"], eps), params["head"],
                "...d,dv->...v")


def held_experts_terms(p, h, ids, weights, held, experts, live=None):
    """The held experts' part of an expert layer's output for ``h [tokens,
    dim]``, and the walk's counts: how many assignments of the ``live``
    tokens met each held expert (``[count]`` int32), the tiles that did
    work and the held experts with at least one row (scalars).  ``held``
    is the ``(first, count)`` of the experts whose stacked weights ``p``
    holds (``experts_gate``, ``experts_up``, ``experts_down``), ``experts``
    the router's width.  Every assignment is a row: sorted by expert, the
    rows of every id outside the held range past the last group, each held
    group cut into tiles of :func:`tile_height`; no capacity, nothing
    dropped.  The loop is as long as the tiles that hold a row, so it has
    no reverse mode."""
    first, count = held
    tokens, k = ids.shape
    rows = tokens * k
    tile = tile_height(rows, experts)
    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < count)
    group = jnp.where(held, local, count)
    order = jnp.argsort(group, stable=True)
    member = group[:, None] == jnp.arange(count)[None, :]
    sizes = jnp.sum(member, axis=0, dtype=jnp.int32)
    tiles = -(-sizes // tile)
    walked = jnp.sum(tiles)
    # where a group begins, in sorted rows and in tiles, and each tile's
    # expert: sums under a mask, because tables made with gathers and
    # cumulative sums made every program slow to load (PERF.md, PR 32)
    before = jnp.arange(count)[None, :] < jnp.arange(count)[:, None]
    start = jnp.sum(jnp.where(before, sizes[None, :], 0), axis=1)
    first_tile = jnp.sum(jnp.where(before, tiles[None, :], 0), axis=1)
    most = min((rows + count * (tile - 1)) // tile, rows)
    expert = jnp.minimum(count - 1, jnp.sum(
        jnp.arange(most)[:, None] >= (first_tile + tiles)[None, :], axis=1))
    # a group's last tile may be cut past the last sorted row
    order = jnp.concatenate([order, jnp.full(tile, rows, order.dtype)])
    weight = weights.reshape(-1)
    kind = p["experts_gate"].dtype
    x = h.astype(kind)
    dot = lambda a, w: jnp.dot(a, w, preferred_element_type=F32)

    def one_tile(i, out):
        e = expert[i]
        at = start[e] + (i - first_tile[e]) * tile  # the tile's first row
        row = jax.lax.dynamic_slice_in_dim(order, at, tile)
        mine = jnp.arange(tile) < start[e] + sizes[e] - at
        token = jnp.where(mine, row // k, tokens)  # the rest goes nowhere
        rows_in = x[jnp.minimum(token, tokens - 1)]
        hidden = (jax.nn.silu(dot(rows_in, p["experts_gate"][e]))
                  * dot(rows_in, p["experts_up"][e])).astype(kind)
        term = (dot(hidden, p["experts_down"][e])
                * weight[jnp.minimum(row, rows - 1)][:, None])
        return out.at[token].add(term, mode="drop")

    out = jax.lax.fori_loop(0, walked, one_tile,
                            jnp.zeros((tokens, h.shape[-1]), F32))
    if live is not None:
        member = member & jnp.repeat(live, k)[:, None]
    return out, (jnp.sum(member, axis=0, dtype=jnp.int32), walked,
                 jnp.sum(sizes > 0, dtype=jnp.int32))


def moe_instruments(registry):
    """The counters that an expert block's walk feeds, under the names the
    benchmark's readers know."""
    return {
        "assignments": registry.counter(
            "serving_moe_assignments_total",
            help="expert assignments routed: live tokens x experts a "
                 "token x expert layers, over all the experts"),
        "held": registry.counter(
            "serving_moe_assignments_held_total",
            help="expert assignments that met an expert held here"),
        "load": registry.histogram(
            "serving_moe_expert_load_max_over_mean",
            help="one observation a decode step: the fullest held "
                 "expert's assignments over the held experts' mean, "
                 "averaged over the expert layers"),
        "tiles": registry.counter(
            "serving_moe_tiles_total",
            help="row tiles of the held experts' products that did "
                 "work, as the programs count them: each reads one "
                 "expert's weights once"),
        "touched": registry.counter(
            "serving_moe_experts_touched_total",
            help="held experts with at least one assignment row in "
                 "a call, summed over the expert layers"),
    }


def observe_walk(instruments, counts, tiles, touched, assigned, step):
    """Feed :func:`moe_instruments` from the expert layers' stacked counts
    (``counts [layers, held]``, ``tiles`` and ``touched`` ``[layers]``) of
    one program that routed ``assigned`` assignments."""
    instruments["tiles"].inc(int(tiles.sum()))
    instruments["touched"].inc(int(touched.sum()))
    instruments["assignments"].inc(assigned)
    instruments["held"].inc(int(counts.sum()))
    mean = counts.mean(axis=1)
    if step and (mean > 0).all():
        instruments["load"].observe(
            float((counts.max(axis=1) / mean).mean()))


@dataclasses.dataclass(frozen=True, eq=False)
class LatentMoELM:
    """See the module docstring.  Field names follow the published
    ``config.json`` of the family; ``max_len`` is the served window (there is
    no position table), ``num_experts`` the router's width and
    ``held_experts`` the ``(first, count)`` of the experts held here (None:
    all)."""

    vocab_size: int
    max_len: int
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    held_experts: Optional[Tuple[int, int]] = None

    # ------------------------------------------------------------- sizes

    @property
    def held(self) -> Tuple[int, int]:
        first, count = self.held_experts or (0, self.num_experts)
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held_experts {self.held_experts} is no part of "
                             f"{self.num_experts} experts")
        return int(first), int(count)

    @property
    def row_width(self) -> int:
        """The cached row: the latent and the rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        q_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        return q_head_dim ** -0.5 * yarn_mscale(
            self.rope_scaling, "mscale_all_dim") ** 2

    def _rotary(self):
        scaling = self.rope_scaling
        amplitude = (yarn_mscale(scaling, "mscale")
                     / yarn_mscale(scaling, "mscale_all_dim"))
        return (jnp.asarray(yarn_inv_freq(self.qk_rope_head_dim,
                                          self.rope_theta, scaling)),
                amplitude)

    def is_expert_layer(self, index: int) -> bool:
        return index >= self.first_k_dense_replace

    # ----------------------------------------------------------- weights

    def param_shapes(self):
        """``{path: (shape, fan_in)}``; ``fan_in`` None marks a norm's weight
        (ones) and 0 the router's bias (drawn at a small scale)."""
        d, h = self.hidden_size, self.num_attention_heads
        q_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        held = self.held[1]
        shapes = {("embed",): ((self.vocab_size, d), d),
                  ("norm",): ((d,), None),
                  ("head",): ((d, self.vocab_size), d)}
        gated = lambda width, lead=(): {
            "gate": (lead + (d, width), d), "up": (lead + (d, width), d),
            "down": (lead + (width, d), width)}
        for i in range(self.num_hidden_layers):
            layer = {
                "attn_norm": ((d,), None), "ffn_norm": ((d,), None),
                "q": ((d, h, q_dim), d), "q_norm": ((q_dim,), None),
                "kv_a": ((d, self.row_width), d),
                "kv_norm": ((self.kv_lora_rank,), None),
                "k_up": ((self.kv_lora_rank, h, self.qk_nope_head_dim),
                         self.kv_lora_rank),
                "v_up": ((self.kv_lora_rank, h, self.v_head_dim),
                         self.kv_lora_rank),
                "o": ((h, self.v_head_dim, d), h * self.v_head_dim)}
            if self.is_expert_layer(i):
                layer["router"] = ((d, self.num_experts), d)
                layer["router_bias"] = ((self.num_experts,), 0)
                for name, entry in gated(self.moe_intermediate_size,
                                         (held,)).items():
                    layer["experts_" + name] = entry
                for name, entry in gated(self.moe_intermediate_size
                                         * self.num_shared_experts).items():
                    layer["shared_" + name] = entry
            else:
                layer.update(gated(self.intermediate_size))
            for name, entry in layer.items():
                shapes[("layers", i, name)] = entry
        return shapes

    def init(self, key, dtype=jnp.float32):
        """A parameter tree from ``key``: matrices normal at ``1 /
        sqrt(fan in)`` in ``dtype``, norms' weights one, the router and its
        bias float32 (the bias non-zero, so that picking differs from
        weighing)."""
        tree = {"layers": [{} for _ in range(self.num_hidden_layers)]}
        return init_params(tree, self.param_shapes(), key, dtype, 0.01)

    # ---------------------------------------------------------- attention

    def latent(self, p, h, positions):
        """A layer's queries and cached row from its normed input ``h
        [batch, rows, dim]``: ``(q_n, q_r [batch, rows, heads, .], c, k_r
        [batch, rows, .])``, float32, rotated."""
        eps = self.rms_norm_eps
        inv_freq, amplitude = self._rotary()
        q = rms_norm(_dot(h, p["q"], "brd,dhe->brhe"), p["q_norm"], eps)
        q_n, q_r = jnp.split(q, [self.qk_nope_head_dim], axis=-1)
        kv = _dot(h, p["kv_a"], "brd,dw->brw")
        c, k_r = jnp.split(kv, [self.kv_lora_rank], axis=-1)
        c = rms_norm(c, p["kv_norm"], eps)
        return (q_n, rope(q_r, positions, inv_freq, amplitude), c,
                rope(k_r, positions, inv_freq, amplitude))

    def expanded_attention(self, p, q_n, q_r, c, k_r):
        """:func:`expanded_attention` at this model's softmax scale."""
        return expanded_attention(p, q_n, q_r, c, k_r, self.softmax_scale)

    def absorbed_step(self, p, pool, tables, pos, q_n, q_r, c, k_r):
        """:func:`absorbed_step` at this model's latent width and scale."""
        return absorbed_step(p, pool, tables, pos, q_n, q_r, c, k_r,
                             self.kv_lora_rank, self.softmax_scale)

    # ------------------------------------------------------- feed-forward

    _gated = staticmethod(gated)

    def route(self, p, h):
        """``(ids, weights) [tokens, k]``: the top k of ``sigmoid(W_r h) +
        b``, weighed by the scores without the bias, normalised to 1 and
        scaled.  Float32 at the highest precision: a near tie at rank k
        falls the same way here as in a plain reference."""
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(F32), p["router"].astype(F32),
            precision=jax.lax.Precision.HIGHEST))
        _, ids = jax.lax.top_k(scores + p["router_bias"].astype(F32),
                               self.num_experts_per_tok)
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        weights = picked / jnp.sum(picked, -1, keepdims=True)
        return ids, weights * self.routed_scaling_factor

    def held_experts_terms(self, p, h, ids, weights, live=None):
        """:func:`held_experts_terms` for this model's held range and its
        router's width."""
        return held_experts_terms(p, h, ids, weights, self.held,
                                  self.num_experts, live)

    def counted_feed_forward(self, p, h, live=None):
        """``(ffn(h) [tokens, dim], the walk's counts or None)`` of one
        layer: None for a dense one, else ``held_experts_terms``' three."""
        if "router" not in p:
            return self._gated(h, p["gate"], p["up"], p["down"]), None
        ids, weights = self.route(p, h)
        routed, walk = self.held_experts_terms(p, h, ids, weights, live)
        shared = self._gated(h, p["shared_gate"], p["shared_up"],
                             p["shared_down"])
        return routed + shared, walk

    def feed_forward(self, p, h, live=None):
        """``(ffn(h) [tokens, dim], held counts or None)`` of one layer."""
        y, walk = self.counted_feed_forward(p, h, live)
        return y, None if walk is None else walk[0]

    # ------------------------------------------------------ full forward

    def __call__(self, params, tokens):
        """Next-token logits ``[batch, rows, vocab]`` (float32) of whole
        sequences ``tokens [batch, rows]``: no cache, expanded attention."""
        tokens = jnp.asarray(tokens, jnp.int32)
        batch, rows = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(rows)[None], tokens.shape)
        eps = self.rms_norm_eps
        x = embed_tokens(params, tokens, positions)
        for p in params["layers"]:
            latent = self.latent(p, rms_norm(x, p["attn_norm"], eps), positions)
            x = x + self.expanded_attention(p, *latent)
            h = rms_norm(x, p["ffn_norm"], eps).reshape(batch * rows, -1)
            x = x + self.feed_forward(p, h)[0].reshape(x.shape)
        return final_head(params, x, eps)

    # ------------------------------------------------------------ serving

    def decode_spec(self, params):
        """What the serving engine serves this model by
        (:class:`distkeras_tpu.models.decode.DecodeSpec`): one pool a layer
        of ``row_width`` (the latent and the rotated shared key), the
        expanded attention for a prefill chunk, the absorbed one for the
        step, and the expert layers' counts of assignments, tiles and
        touched experts as the block's own counters.  No ``window`` and no
        ``shard``: the engine refuses ``draft_model=`` and ``mesh=`` for
        this block."""
        from distkeras_tpu.models.decode import DecodeSpec

        eps = self.rms_norm_eps
        expert_layers = sum(self.is_expert_layer(i)
                            for i in range(self.num_hidden_layers))

        # a layer's work is traced and lowered once a shape, not once a
        # layer: the expert layers are alike (XLA inlines the calls)
        @jax.jit
        def prefill_layer(p, x, positions, live):
            q_n, q_r, c, k_r = self.latent(
                p, rms_norm(x, p["attn_norm"], eps), positions)
            x = x + self.expanded_attention(p, q_n, q_r, c, k_r)
            y, walk = self.counted_feed_forward(
                p, rms_norm(x, p["ffn_norm"], eps)[0], live[0])
            return x + y[None], jnp.concatenate([c, k_r], axis=-1)[0], walk

        def prefill(params, li, x, positions, write, live):
            x, row, walk = prefill_layer(params["layers"][li], x, positions,
                                         live)
            write("latent", row)
            return x, walk

        @jax.jit
        def step_layer(p, x, pool, tables, pos, live):
            latent = self.latent(p, rms_norm(x, p["attn_norm"], eps),
                                 pos[:, None])
            pool, out = self.absorbed_step(p, pool, tables, pos, *latent)
            x = x + out
            y, walk = self.counted_feed_forward(
                p, rms_norm(x, p["ffn_norm"], eps)[:, 0], live[:, 0])
            return pool, x + y[:, None], walk

        def step(params, li, x, pools, tables, pos, live):
            pool, x, walk = step_layer(params["layers"][li], x,
                                       pools["latent"], tables, pos, live)
            return {"latent": pool}, x, walk

        def head(params, x, at=None):
            return final_head(params, x, eps, at)

        def observe(instruments, aux, rows, step):
            counts, tiles, touched = map(np.stack, zip(
                *(a for a in aux if a is not None)))
            observe_walk(instruments, counts, tiles, touched,
                         rows * self.num_experts_per_tok * len(counts), step)

        return DecodeSpec(
            state=(("latent", self.row_width),), weights=params,
            num_layers=self.num_hidden_layers, max_len=int(self.max_len),
            vocab_size=int(self.vocab_size),
            geometry=geometry_of(self),
            embed=embed_tokens, prefill=prefill, step=step, head=head,
            instruments=moe_instruments if expert_layers else None,
            observe=observe if expert_layers else None)
