"""A causal LM of **double layers with a shortcut expert branch**.

The layer of LongCat-Flash's family ("shortcut-connected" experts): two
sub-blocks, each a latent attention (MLA) and a dense gated feed-forward, and
ONE expert branch that leaves the residual path after the first attention
and rejoins it at the layer's end, so that three of the four dense parts lie
between its input and its result.  Its router has more outputs than there
are experts with weights: the ids past ``n_routed_experts`` are
**zero-compute experts**, whose term is ``weight x input`` (identity), so a
token's work varies from none to ``moe_topk`` real experts.

Equations (``x [tokens, dim]`` the residual stream, ``j`` in {0, 1} the
sub-block, every ``norm`` an RMSNorm with its own weight):

* ``MLA_j(h)``: ``c_q = norm(h W_qa)``, ``q = (c_q W_qb) [heads, nope +
  rope] * s_q`` with ``s_q = sqrt(hidden / q_lora_rank)``
  (``mla_scale_q_lora``), ``q = [q_n, rope(q_r)]``; ``[c, k_r] = h W_kva``,
  ``c = norm(c) * s_kv`` with ``s_kv = sqrt(hidden / kv_lora_rank)``
  (``mla_scale_kv_lora``), ``k_r = rope(k_r)``, one for all heads; the
  cached row is ``[c, k_r]``; ``k_n = c W_uk``, ``v = c W_uv``; scores ``(q_n
  . k_n + q_r . k_r) / sqrt(nope + rope)``, causal softmax in float32, out
  through ``W_o``.  Plain rotary at ``rope_theta``, the halves paired.
* ``FFN_j(h) = (silu(h W_g) * (h W_u)) W_d``.
* ``MoE(u)``: ``s = softmax(u W_r)`` over all the router's outputs, float32;
  the top ``moe_topk`` of ``s + b`` (the bias picks, it does not weigh);
  ``w_e = routed_scaling_factor * s_e``, **not normalised**; the sum over the
  picked ``e < n_routed_experts`` of ``w_e Expert_e(u)`` plus ``(sum of the
  picked zero-compute experts' w_e) * u``.  No shared expert.
* the layer: ``x1 = x + MLA_0(norm(x)); u = norm(x1); x2 = x1 + FFN_0(u); x3
  = x2 + MLA_1(norm(x2)); x4 = x3 + FFN_1(norm(x3)); y = x4 + MoE(u)``.

**The chip's share.**  ``held_experts = (first, count)`` within the routed
experts: the layer routes over all the outputs, computes its held experts'
terms with the walk over row tiles (every other id, absent or zero-compute,
sorts past the last group and costs the walk nothing) and the zero-compute
experts' term **for every token it runs**: an identity expert has no
weights and lies on no chip, so a token's own chip adds it.  What absent
experts would have added is left out.

Everything that this block has in common with ``LatentMoELM`` is that
module's free functions (``models/latent_moe.py`` lists them): the expanded
attention of a chunk, the absorbed step over the paged rows, ``rms_norm``,
``rope``, the gated feed-forward, the walk and the counters' instruments.
Here are only the query's low-rank path with the two LoRA scales, the
router, the zero-compute term and the layer's graph.  Precision as there:
operands in the weights' type, float32 accumulation, stream, norms, router
(at the highest precision), softmax and running attention state float32.

``decode_spec(params)`` declares **two pools of the same kind a layer**
(``latent_0``, ``latent_1``): ``prefill`` writes both sub-blocks' rows of a
chunk, ``step`` writes and reads both pools, and the expert branch is
computed from ``u`` and carried to the layer's end in both.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.latent_moe import (
    F32, _dot, absorbed_step, embed_tokens, expanded_attention, final_head,
    gated, geometry_of, held_experts_terms, init_params, moe_instruments,
    observe_walk, rms_norm, rope, yarn_inv_freq)

__all__ = ["ShortcutMoELM"]

#: the standard deviation that ``init`` draws the picking bias at: small
#: against the scores, which are of order ``1 / router width``
BIAS_SCALE = 1e-4


@dataclasses.dataclass(frozen=True, eq=False)
class ShortcutMoELM:
    """See the module docstring.  Field names follow the family's published
    ``config.json``; ``max_len`` is the served window (there is no position
    table), ``num_layers`` counts the double layers, and ``held_experts`` is
    the ``(first, count)`` of the routed experts held here (None: all)."""

    vocab_size: int
    max_len: int
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    held_experts: Optional[Tuple[int, int]] = None

    # ------------------------------------------------------------- sizes

    @property
    def held(self) -> Tuple[int, int]:
        first, count = self.held_experts or (0, self.n_routed_experts)
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"held_experts {self.held_experts} is no part of "
                             f"{self.n_routed_experts} routed experts")
        return int(first), int(count)

    @property
    def router_width(self) -> int:
        """The router's outputs: the routed and the zero-compute experts."""
        return self.n_routed_experts + self.zero_expert_num

    @property
    def row_width(self) -> int:
        """One sub-block's cached row: the latent and the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def _lora_scales(self) -> Tuple[float, float]:
        """``(s_q, s_kv)``: 1 where the config's flag is off."""
        scale = lambda on, rank: (
            (self.hidden_size / rank) ** 0.5 if on else 1.0)
        return (scale(self.mla_scale_q_lora, self.q_lora_rank),
                scale(self.mla_scale_kv_lora, self.kv_lora_rank))

    # ----------------------------------------------------------- weights

    def param_shapes(self):
        """``{path: (shape, 1 / variance)}``: every matrix is drawn at ONE
        standard deviation, ``1 / sqrt(hidden_size)``, whatever its fan in:
        that is what the two LoRA scales presuppose (they bring the low-rank
        paths' outputs up to a full-rank projection's; under a ``1 /
        sqrt(fan in)`` draw they would blow the attention's scores up to a
        standard deviation of some 6 and make the network chaotic).  None
        marks a norm's weight (ones) and 0 the router's bias (drawn at
        :data:`BIAS_SCALE`)."""
        d, h = self.hidden_size, self.num_attention_heads
        q_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        held, wide = self.held[1], self.expert_ffn_hidden_size
        block = {
            "attn_norm": (d,), "ffn_norm": (d,),
            "q_a": (d, self.q_lora_rank), "q_a_norm": (self.q_lora_rank,),
            "q_b": (self.q_lora_rank, h, q_dim),
            "kv_a": (d, self.row_width), "kv_norm": (self.kv_lora_rank,),
            "k_up": (self.kv_lora_rank, h, self.qk_nope_head_dim),
            "v_up": (self.kv_lora_rank, h, self.v_head_dim),
            "o": (h, self.v_head_dim, d),
            "gate": (d, self.ffn_hidden_size), "up": (d, self.ffn_hidden_size),
            "down": (self.ffn_hidden_size, d)}
        branch = {
            "router": (d, self.router_width),
            "router_bias": (self.router_width,),
            "experts_gate": (held, d, wide), "experts_up": (held, d, wide),
            "experts_down": (held, wide, d)}
        paths = {("embed",): (self.vocab_size, d), ("norm",): (d,),
                 ("head",): (d, self.vocab_size)}
        for i in range(self.num_layers):
            for name, shape in branch.items():
                paths[("layers", i, name)] = shape
            for j in range(2):
                for name, shape in block.items():
                    paths[("layers", i, "blocks", j, name)] = shape
        drawn = lambda path, shape: (
            0 if path[-1] == "router_bias" else None if len(shape) == 1 else d)
        return {path: (shape, drawn(path, shape))
                for path, shape in paths.items()}

    def init(self, key, dtype=jnp.float32):
        """A parameter tree from ``key``: a double layer is ``{"blocks":
        [sub-block 0, sub-block 1], "router", "router_bias", "experts_gate",
        "experts_up", "experts_down"}``; matrices normal at ``1 /
        sqrt(hidden_size)`` in ``dtype`` (:meth:`param_shapes` has why),
        norms' weights one, the router and its bias float32 (the bias
        non-zero, so that picking differs from weighing)."""
        tree = {"layers": [{"blocks": [{}, {}]}
                           for _ in range(self.num_layers)]}
        return init_params(tree, self.param_shapes(), key, dtype, BIAS_SCALE)

    # ---------------------------------------------------------- attention

    def latent(self, b, h, positions):
        """A sub-block's queries and cached row from its normed input ``h
        [batch, rows, dim]``: ``(q_n, q_r [batch, rows, heads, .], c, k_r
        [batch, rows, .])``, float32, scaled and rotated."""
        eps = self.rms_norm_eps
        s_q, s_kv = self._lora_scales()
        inv_freq = jnp.asarray(yarn_inv_freq(self.qk_rope_head_dim,
                                             self.rope_theta, None))
        c_q = rms_norm(_dot(h, b["q_a"], "brd,dq->brq"), b["q_a_norm"], eps)
        q = _dot(c_q, b["q_b"], "brq,qhe->brhe") * s_q
        q_n, q_r = jnp.split(q, [self.qk_nope_head_dim], axis=-1)
        kv = _dot(h, b["kv_a"], "brd,dw->brw")
        c, k_r = jnp.split(kv, [self.kv_lora_rank], axis=-1)
        c = rms_norm(c, b["kv_norm"], eps) * s_kv
        return (q_n, rope(q_r, positions, inv_freq), c,
                rope(k_r, positions, inv_freq))

    # ------------------------------------------------------ expert branch

    def route(self, p, u):
        """``(ids, weights) [tokens, moe_topk]``: the top k of ``softmax(u
        W_r) + b`` over all the router's outputs, weighed by the scores
        without the bias, scaled and **not normalised**.  Float32 at the
        highest precision: a near tie at rank k falls the same way here as
        in a plain reference."""
        scores = jax.nn.softmax(jnp.dot(
            u.astype(F32), p["router"].astype(F32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        _, ids = jax.lax.top_k(scores + p["router_bias"].astype(F32),
                               self.moe_topk)
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        return ids, picked * self.routed_scaling_factor

    def expert_branch(self, p, u, live=None):
        """``(MoE(u) [tokens, dim], counts)``: the held experts' terms by
        the walk over row tiles plus the zero-compute experts' term, ``(sum
        of their weights) x u``, for every token.  ``counts`` is the walk's
        three (:func:`~distkeras_tpu.models.latent_moe.held_experts_terms`)
        and two of the ``live`` tokens' picks: how many met a zero-compute
        expert, and the most real (not zero-compute) experts that any one
        token picked."""
        ids, weights = self.route(p, u)
        routed, walk = held_experts_terms(p, u, ids, weights, self.held,
                                          self.router_width, live)
        zero = ids >= self.n_routed_experts
        out = routed + (jnp.sum(jnp.where(zero, weights, 0.0), axis=-1,
                                keepdims=True) * u)
        mine = (jnp.ones(ids.shape[:1], bool) if live is None
                else live)[:, None]
        real = jnp.sum(~zero & mine, axis=-1, dtype=jnp.int32)
        return out, walk + (jnp.sum(zero & mine, dtype=jnp.int32),
                            jnp.max(real))

    # ------------------------------------------------------------ a layer

    def layer(self, p, x, attend, live=None):
        """One double layer over ``x [batch, rows, dim]``: ``(y, the expert
        branch's counts)``.  ``attend(j, b, h)`` is sub-block ``j``'s
        attention of its normed input ``h`` (expanded over a chunk, or
        absorbed over a pool: the caller's).  The expert branch is computed
        from ``u``, the first sub-block's normed stream, and added after the
        second sub-block's feed-forward: the shortcut."""
        eps = self.rms_norm_eps
        first, second = p["blocks"]
        flat = lambda t: t.reshape(-1, t.shape[-1])
        x = x + attend(0, first, rms_norm(x, first["attn_norm"], eps))
        u = flat(rms_norm(x, first["ffn_norm"], eps))
        branch, counts = self.expert_branch(
            p, u, None if live is None else live.reshape(-1))
        x = x + gated(u, first["gate"], first["up"],
                      first["down"]).reshape(x.shape)
        x = x + attend(1, second, rms_norm(x, second["attn_norm"], eps))
        h = flat(rms_norm(x, second["ffn_norm"], eps))
        x = x + gated(h, second["gate"], second["up"],
                      second["down"]).reshape(x.shape)
        return x + branch.reshape(x.shape), counts

    def _expanded(self, positions, rows=None):
        """``attend`` for whole chunks that start at position 0; each
        sub-block's ``[c, k_r]`` rows are appended to ``rows``."""
        def attend(j, b, h):
            q_n, q_r, c, k_r = self.latent(b, h, positions)
            if rows is not None:
                rows.append(jnp.concatenate([c, k_r], axis=-1))
            return expanded_attention(b, q_n, q_r, c, k_r, self.softmax_scale)
        return attend

    # ------------------------------------------------------ full forward

    def __call__(self, params, tokens):
        """Next-token logits ``[batch, rows, vocab]`` (float32) of whole
        sequences ``tokens [batch, rows]``: no cache, expanded attention."""
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                                     tokens.shape)
        x = embed_tokens(params, tokens, positions)
        for p in params["layers"]:
            x, _ = self.layer(p, x, self._expanded(positions))
        return final_head(params, x, self.rms_norm_eps)

    # ------------------------------------------------------------ serving

    def decode_spec(self, params):
        """What the serving engine serves this model by
        (:class:`distkeras_tpu.models.decode.DecodeSpec`): two pools of
        ``row_width`` a double layer (``latent_0`` and ``latent_1``, one a
        sub-block), the expanded attention for a prefill chunk, the absorbed
        one for the step, and the expert branch's counts as the block's own
        counters: the five that ``LatentMoELM`` registers, under the same
        names, and two of the zero-compute experts.  No ``window`` and no
        ``shard``: the engine refuses ``draft_model=`` and ``mesh=`` for
        this block."""
        from distkeras_tpu.models.decode import DecodeSpec

        eps = self.rms_norm_eps
        names = ("latent_0", "latent_1")

        # a layer's work is traced and lowered once a shape, not once a
        # layer: the double layers are alike (XLA inlines the calls)
        @jax.jit
        def prefill_layer(p, x, positions, live):
            rows = []
            x, counts = self.layer(p, x, self._expanded(positions, rows), live)
            return x, [row[0] for row in rows], counts

        def prefill(params, li, x, positions, write, live):
            x, rows, counts = prefill_layer(params["layers"][li], x,
                                            positions, live)
            for name, row in zip(names, rows):
                write(name, row)
            return x, counts

        @jax.jit
        def step_layer(p, x, pools, tables, pos, live):
            pools = list(pools)

            def attend(j, b, h):
                pools[j], out = absorbed_step(
                    b, pools[j], tables, pos, *self.latent(b, h, pos[:, None]),
                    self.kv_lora_rank, self.softmax_scale)
                return out

            x, counts = self.layer(p, x, attend, live)
            return pools, x, counts

        def step(params, li, x, pools, tables, pos, live):
            new, x, counts = step_layer(params["layers"][li], x,
                                        [pools[name] for name in names],
                                        tables, pos, live)
            return dict(zip(names, new)), x, counts

        def head(params, x, at=None):
            return final_head(params, x, eps, at)

        def instruments(registry):
            return dict(
                moe_instruments(registry),
                zero=registry.counter(
                    "serving_moe_assignments_zero_total",
                    help="expert assignments of live tokens that met a "
                         "zero-compute (identity) expert: no weights, no "
                         "exchange"),
                real=registry.histogram(
                    "serving_moe_real_picks_max_over_mean",
                    help="one observation a decode step: the most real "
                         "(not zero-compute) experts that any live token "
                         "picked over the live tokens' mean, averaged over "
                         "the layers"))

        def observe(instruments, aux, rows, step):
            counts, tiles, touched, zero, most = map(np.stack, zip(*aux))
            observe_walk(instruments, counts, tiles, touched,
                         rows * self.moe_topk * len(counts), step)
            instruments["zero"].inc(int(zero.sum()))
            mean = self.moe_topk - zero / max(rows, 1)  # real picks a token
            if step and (mean > 0).all():
                instruments["real"].observe(float((most / mean).mean()))

        return DecodeSpec(
            state=tuple((name, self.row_width) for name in names),
            weights=params, num_layers=self.num_layers,
            max_len=int(self.max_len), vocab_size=int(self.vocab_size),
            geometry=geometry_of(self),
            embed=embed_tokens, prefill=prefill, step=step, head=head,
            instruments=instruments, observe=observe)
