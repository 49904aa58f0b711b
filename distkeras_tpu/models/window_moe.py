"""A causal LM of **sliding-window and global grouped-query layers** over
sparse expert layers.

The block of MiMo-V2-Flash's family (``mimo_v2``): most layers attend a
window of the last positions alone, with a learned sink logit a head, and
every few layers a global layer attends the whole context; both kinds are
grouped-query (a key-value head serves several query heads) with different
head counts and rotary bases, and both keep keys and values of different
widths.  One leading dense gated feed-forward layer, then expert layers.

Equations (``x`` a layer's input, ``norm`` RMSNorm with a learned weight;
``c`` the layer's kind, global or sliding, with its own head counts, widths
and rotary base):

* layer: ``x = x + attn_c(norm(x)); x = x + ffn(norm(x))``; a final ``norm``
  and an untied head;
* ``attn_c(h)``: ``q = h W_q [heads, head_dim]``, ``k = h W_k [kv_heads,
  head_dim]``, ``v = h W_v [kv_heads, v_dim]``; the first ``rotary_dim =
  int(head_dim x partial_rotary_factor)`` dimensions of ``q`` and ``k``
  rotated (the halves paired) at the kind's base, the rest as they are;
  key-value head ``i`` serves query heads ``i x group .. (i + 1) x group -
  1``; scores ``q . k / sqrt(head_dim)`` in float32; a global layer's query
  at ``i`` sees the keys ``j <= i``, a sliding one's ``i - window < j <=
  i``, and with sinks a head's learned logit stands in the softmax's
  denominator: ``p_j = exp(s_j) / (exp(sink) + sum_k exp(s_k))``; ``o = W_o
  (attention_value_scale x sum_j p_j v_j)``;
* feed-forward: a dense layer's ``W_down(silu(W_gate h) * W_up h)``; an
  expert layer's router ``s = sigmoid(W_r h)`` in float32, the top k of ``s
  + b`` (the bias picks, it does not weigh), weights ``s_i / sum_topk(s)``
  scaled by ``routed_scaling_factor`` (None: 1), each expert a gated
  feed-forward; no shared expert.

**The chip's share.**  ``held_experts = (first, count)``, as
``LatentMoELM`` takes it: the layer routes over all the experts and computes
its held experts' terms by the walk over row tiles, dropping nothing; what
absent experts would add is left out.  Router and walk are
``models/latent_moe.py``'s (:func:`~distkeras_tpu.models.latent_moe.
sigmoid_route`, :func:`~distkeras_tpu.models.latent_moe.held_experts_terms`),
as are the norm, the rotary, the gated feed-forward, the head and the
counters of the walk.

**Serving** (``decode_spec``): a global layer keeps ``k`` and ``v`` rows of
its key-value heads for every position, paged through the slots' tables and
read by :func:`~distkeras_tpu.serving.cache.paged_decode_attention`; a
sliding layer keeps ``sliding_k`` and ``sliding_v`` rows of the last
``sliding_window`` positions of a slot alone, in a per-slot ring
(``DecodeSpec.sliding``), read by :func:`~distkeras_tpu.serving.cache.
sliding_decode_attention` with the sinks.  A prefill chunk's attention goes
a block of ``QUERY_BLOCK`` queries at a time: a global layer's against the
keys up to the block's end, a sliding layer's against its band alone (at
most ``QUERY_BLOCK + window - 1`` keys), never ``[heads, width, width]``.
The block counts what the mechanism saves: per decode step the positions
its sliding reads cover against what a full-context read would, and per
prefill the query-key pairs its blocks score against a dense causal
attention's.  No ``window`` (speculative verify) and no ``shard``
(tensor-parallel) twin: the engine refuses ``draft_model=`` and ``mesh=``
for this block.

Precision as in the other expert blocks: products take their operands in the
weights' type and accumulate in float32; residual stream, norms, router
(at the highest precision), softmax and running attention state float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.latent_moe import (
    F32, QUERY_BLOCK, _dot, embed_tokens, final_head, gated, geometry_of,
    held_experts_terms, init_params, moe_instruments, observe_walk, rms_norm,
    rope, sigmoid_route, yarn_inv_freq)

__all__ = ["WindowMoELM"]

#: global layer 0, sliding 1, as ``hybrid_layer_pattern`` writes them
GLOBAL, SLIDING = 0, 1


def query_blocks(rows, window=None):
    """``(start, end, first key)`` of each block of queries of a chunk of
    ``rows`` that starts at position 0: blocks of :data:`QUERY_BLOCK`, each
    against the keys up to its end, a sliding layer's (``window``) only from
    the first key that its first query sees."""
    for start in range(0, rows, QUERY_BLOCK):
        end = min(rows, start + QUERY_BLOCK)
        yield start, end, 0 if window is None else max(0, start - window + 1)


def attention_pairs(rows, window=None):
    """The query-key pairs that :func:`block_attention` scores over a chunk
    of ``rows``, as :func:`query_blocks` lays them."""
    return sum((end - start) * (end - first)
               for start, end, first in query_blocks(rows, window))


def block_attention(q, k, v, start, first, scale, window=None, sinks=None):
    """Grouped-query attention of one block of queries of a chunk that
    starts at position 0: ``q [batch, block, heads, head_dim]`` at positions
    ``start ..``, against ``k [batch, keys, kv_heads, head_dim]`` and ``v
    [batch, keys, kv_heads, v_dim]`` at positions ``first ..``, operands in
    ``q``'s type, scores and weights float32.  ``window`` None: causal over
    every earlier key; else the keys ``i - window < j <= i``.  ``sinks
    [heads]`` (or None) stand in the denominator.  ``[batch, block, heads,
    v_dim]`` float32."""
    batch, block, heads, head_dim = q.shape
    kv_heads = k.shape[2]
    group = heads // kv_heads
    s = jnp.einsum("bqkge,bjke->bkgqj",
                   q.reshape(batch, block, kv_heads, group, head_dim), k,
                   preferred_element_type=F32) * scale
    i = jnp.arange(start, start + block)[:, None]
    j = jnp.arange(first, first + k.shape[1])[None, :]
    seen = j <= i if window is None else (j <= i) & (j > i - window)
    s = jnp.where(seen, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sinks is not None:
        sink = sinks.astype(F32).reshape(1, kv_heads, group, 1, 1)
        m = jnp.maximum(m, sink)
    p = jnp.exp(s - m)
    total = jnp.sum(p, axis=-1, keepdims=True)
    if sinks is not None:
        total = total + jnp.exp(sink - m)
    o = jnp.einsum("bkgqj,bjkv->bqkgv", (p / total).astype(q.dtype), v,
                   preferred_element_type=F32)
    return o.reshape(batch, block, heads, -1)


@dataclasses.dataclass(frozen=True, eq=False)
class WindowMoELM:
    """See the module docstring.  Field names follow the family's published
    ``config.json``; ``max_len`` is the served window (there is no position
    table), ``hybrid_layer_pattern`` gives each layer's kind (0 global, 1
    sliding) and ``moe_layer_freq`` whether it is an expert layer (1) or
    dense (0), a list as long as the layers (None: the family's rule, layer
    0 and every sixth from layer 5 on global, layer 0 dense), and
    ``held_experts`` the ``(first, count)`` of the experts held here (None:
    all)."""

    vocab_size: int
    max_len: int
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    hybrid_layer_pattern: Optional[Tuple[int, ...]] = None
    moe_layer_freq: Optional[Tuple[int, ...]] = None
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    add_full_attention_sink_bias: bool = False
    add_swa_attention_sink_bias: bool = True
    attention_value_scale: float = 0.707
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    routed_scaling_factor: Optional[float] = None
    layernorm_epsilon: float = 1e-5
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.add_full_attention_sink_bias:
            # the global layers' step reads its pages with no sink
            # (paged_decode_attention): prefill and step would differ
            raise ValueError("add_full_attention_sink_bias: the global "
                             "layers have no sink")
        layers = self.num_hidden_layers
        rule = {"hybrid_layer_pattern": [
                    GLOBAL if i == 0 or i % 6 == 5 else SLIDING
                    for i in range(layers)],
                "moe_layer_freq": [int(i > 0) for i in range(layers)]}
        for name, default in rule.items():
            value = getattr(self, name)
            value = tuple(int(v) for v in (default if value is None else value))
            if len(value) != layers:
                raise ValueError(f"{name} has {len(value)} entries for "
                                 f"{layers} layers")
            object.__setattr__(self, name, value)
        if self.held_experts is not None:
            object.__setattr__(self, "held_experts",
                               tuple(int(v) for v in self.held_experts))

    # ------------------------------------------------------------- sizes

    @property
    def held(self) -> Tuple[int, int]:
        first, count = self.held_experts or (0, self.n_routed_experts)
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"held_experts {self.held_experts} is no part of "
                             f"{self.n_routed_experts} experts")
        return int(first), int(count)

    def is_sliding(self, index: int) -> bool:
        return self.hybrid_layer_pattern[index] == SLIDING

    def is_expert_layer(self, index: int) -> bool:
        return bool(self.moe_layer_freq[index])

    def heads(self, sliding: bool) -> Tuple[int, int, int, int]:
        """``(heads, kv_heads, head_dim, v_dim)`` of a kind of layer."""
        if sliding:
            return (self.swa_num_attention_heads, self.swa_num_key_value_heads,
                    self.swa_head_dim, self.swa_v_head_dim)
        return (self.num_attention_heads, self.num_key_value_heads,
                self.head_dim, self.v_head_dim)

    def rotary_dim(self, sliding: bool) -> int:
        return int(self.heads(sliding)[2] * self.partial_rotary_factor)

    def has_sinks(self, sliding: bool) -> bool:
        return sliding and self.add_swa_attention_sink_bias

    @property
    def scaling(self) -> float:
        return (1.0 if self.routed_scaling_factor is None
                else float(self.routed_scaling_factor))

    # ----------------------------------------------------------- weights

    def param_shapes(self):
        """``{path: (shape, fan_in)}``; ``fan_in`` None marks a norm's weight
        (ones), 0 the router's bias (drawn at a small scale) and 1 a sink
        logit (drawn at one)."""
        d = self.hidden_size
        shapes = {("embed",): ((self.vocab_size, d), d),
                  ("norm",): ((d,), None),
                  ("head",): ((d, self.vocab_size), d)}
        gated_shapes = lambda width, lead=(): {
            "gate": (lead + (d, width), d), "up": (lead + (d, width), d),
            "down": (lead + (width, d), width)}
        for i in range(self.num_hidden_layers):
            sliding = self.is_sliding(i)
            heads, kv_heads, head_dim, v_dim = self.heads(sliding)
            layer = {"attn_norm": ((d,), None), "ffn_norm": ((d,), None),
                     "q": ((d, heads, head_dim), d),
                     "k": ((d, kv_heads, head_dim), d),
                     "v": ((d, kv_heads, v_dim), d),
                     "o": ((heads, v_dim, d), heads * v_dim)}
            if self.has_sinks(sliding):
                layer["sink"] = ((heads,), 1)
            if self.is_expert_layer(i):
                layer["router"] = ((d, self.n_routed_experts), d)
                layer["router_bias"] = ((self.n_routed_experts,), 0)
                for name, entry in gated_shapes(self.moe_intermediate_size,
                                                (self.held[1],)).items():
                    layer["experts_" + name] = entry
            else:
                layer.update(gated_shapes(self.intermediate_size))
            for name, entry in layer.items():
                shapes[("layers", i, name)] = entry
        return shapes

    def init(self, key, dtype=jnp.float32):
        """A parameter tree from ``key``: matrices normal at ``1 /
        sqrt(fan in)`` in ``dtype``, norms' weights one, the router and its
        bias float32 (the bias non-zero, so that picking differs from
        weighing), the sink logits normal at one, float32."""
        tree = {"layers": [{} for _ in range(self.num_hidden_layers)]}
        tree = init_params(tree, self.param_shapes(), key, dtype, 0.01)
        for layer in tree["layers"]:
            if "sink" in layer:
                layer["sink"] = layer["sink"].astype(F32)
        return tree

    # ---------------------------------------------------------- attention

    def rotate(self, t, positions, sliding):
        """``t [batch, rows, heads, head_dim]`` with its leading
        ``rotary_dim`` dimensions rotated at the kind's base by ``positions
        [batch, rows]``, the rest as they are."""
        rotary = self.rotary_dim(sliding)
        theta = self.swa_rope_theta if sliding else self.rope_theta
        inv_freq = jnp.asarray(yarn_inv_freq(rotary, theta, None))
        return jnp.concatenate(
            [rope(t[..., :rotary], positions, inv_freq), t[..., rotary:]], -1)

    def queries(self, p, h, positions, sliding):
        """Rotated queries of the normed input ``h [batch, rows, dim]``,
        float32."""
        return self.rotate(_dot(h, p["q"], "brd,dhe->brhe"), positions,
                           sliding)

    def keys_values(self, p, h, positions, sliding):
        """Rotated keys and the values of ``h``, float32."""
        return (self.rotate(_dot(h, p["k"], "brd,dhe->brhe"), positions,
                            sliding),
                _dot(h, p["v"], "brd,dhe->brhe"))

    def softmax_scale(self, sliding: bool) -> float:
        return self.heads(sliding)[2] ** -0.5

    def output(self, p, out):
        """``W_o (attention_value_scale x out)``, ``out [batch, rows,
        heads, v_dim]``."""
        return _dot(out * self.attention_value_scale, p["o"],
                    "brhv,hvd->brd")

    def chunk_attention(self, p, h, positions, sliding):
        """``(attn(h) [batch, rows, dim], k, v)`` of a chunk that starts at
        position 0: the chunk's keys and values whole, then a block of
        queries at a time (:func:`query_blocks`) its queries, its attention
        (:func:`block_attention`) and its part of the output projection, so
        that nothing as wide as every query head of the chunk is held."""
        window = self.sliding_window if sliding else None
        sinks = p.get("sink") if self.has_sinks(sliding) else None
        kind = p["q"].dtype
        k, v = self.keys_values(p, h, positions, sliding)
        keys, values = k.astype(kind), v.astype(kind)
        out = []
        for start, end, first in query_blocks(h.shape[1], window):
            q = self.queries(p, h[:, start:end], positions[:, start:end],
                             sliding)
            out.append(self.output(p, block_attention(
                q.astype(kind), keys[:, first:end], values[:, first:end],
                start, first, self.softmax_scale(sliding), window, sinks)))
        return jnp.concatenate(out, axis=1), k, v

    # ------------------------------------------------------- feed-forward

    def feed_forward(self, p, h, live=None):
        """``(ffn(h) [tokens, dim], the walk's counts or None)`` of one
        layer: None for a dense one, else ``held_experts_terms``' three."""
        if "router" not in p:
            return gated(h, p["gate"], p["up"], p["down"]), None
        ids, weights = sigmoid_route(p, h, self.num_experts_per_tok,
                                     self.scaling)
        return held_experts_terms(p, h, ids, weights, self.held,
                                  self.n_routed_experts, live)

    # ------------------------------------------------------ full forward

    def __call__(self, params, tokens):
        """Next-token logits ``[batch, rows, vocab]`` (float32) of whole
        sequences ``tokens [batch, rows]``: no cache."""
        tokens = jnp.asarray(tokens, jnp.int32)
        batch, rows = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(rows)[None], tokens.shape)
        eps = self.layernorm_epsilon
        x = embed_tokens(params, tokens, positions)
        for i, p in enumerate(params["layers"]):
            x = x + self.chunk_attention(p, rms_norm(x, p["attn_norm"], eps),
                                         positions, self.is_sliding(i))[0]
            h = rms_norm(x, p["ffn_norm"], eps).reshape(batch * rows, -1)
            x = x + self.feed_forward(p, h)[0].reshape(x.shape)
        return final_head(params, x, eps)

    # ------------------------------------------------------------ serving

    def decode_spec(self, params):
        """What the serving engine serves this model by
        (:class:`distkeras_tpu.models.decode.DecodeSpec`): ``k`` and ``v``
        paged in the global layers, ``sliding_k`` and ``sliding_v`` in a
        ring of ``sliding_window`` rows a slot in the sliding ones
        (``layer_kinds``, ``sliding``); the blocked attention for a prefill
        chunk, the paged grouped-query read or the ring's read with the
        sinks for the step.  The block's own counters: the five
        ``serving_moe_*`` of the expert layers' walk that ``LatentMoELM``
        registers, under the same names, and four of what the windows
        save: ``serving_sliding_positions_{read,live}_total`` (per decode
        step, live slot and sliding layer, the positions the ring's read
        covers, ``sliding_window``, and what a full-context read would cover,
        ``pos + 1``) and ``serving_prefill_attn_{pairs,causal_pairs}_total``
        (per prefill, the query-key pairs that its blocks score, from the
        chunk's static width, and what a dense causal attention over that
        width would, ``layers x W(W + 1) / 2``).  A step hands back its live
        slots and their positions' sum, a prefill its width, as one small
        array in its first layer's counts, so that the host reads one more
        array a program and not one a layer, and counts over the layers in
        Python ints, which do not overflow.  No ``window`` and no
        ``shard``: the engine refuses ``draft_model=`` and ``mesh=`` for
        this block."""
        from distkeras_tpu.models.decode import DecodeSpec
        from distkeras_tpu.serving.cache import (paged_decode_attention,
                                                 sliding_decode_attention)

        eps = self.layernorm_epsilon
        window = self.sliding_window
        layers = range(self.num_hidden_layers)
        sliding_layers = [i for i in layers if self.is_sliding(i)]
        names = {False: ("k", "v"), True: ("sliding_k", "sliding_v")}
        state = []
        for sliding in sorted({self.is_sliding(i) for i in layers}):
            _, kv_heads, head_dim, v_dim = self.heads(sliding)
            state += zip(names[sliding], (kv_heads * head_dim,
                                          kv_heads * v_dim))

        # a layer's work is traced and lowered once a kind and shape, not
        # once a layer (XLA inlines the calls)
        @functools.partial(jax.jit, static_argnames="sliding")
        def prefill_layer(p, x, positions, live, sliding):
            out, k, v = self.chunk_attention(
                p, rms_norm(x, p["attn_norm"], eps), positions, sliding)
            x = x + out
            y, walk = self.feed_forward(p, rms_norm(x, p["ffn_norm"], eps)[0],
                                        live[0])
            return x + y[None], k[0], v[0], walk

        def prefill(params, li, x, positions, write, live):
            sliding = self.is_sliding(li)
            x, k, v, walk = prefill_layer(params["layers"][li], x, positions,
                                          live, sliding)
            for name, rows in zip(names[sliding], (k, v)):
                write(name, rows.reshape(rows.shape[0], -1))
            counts = {} if walk is None else {"walk": walk}
            if li == 0:
                # the chunk's static width, one constant to read: the host
                # counts its pairs (observe), in ints that cannot overflow
                counts["width"] = jnp.asarray(x.shape[1], jnp.int32)
            return x, counts

        @functools.partial(jax.jit, static_argnames="sliding")
        def step_layer(p, x, keys, values, tables, pos, live, sliding):
            h = rms_norm(x, p["attn_norm"], eps)
            q = self.queries(p, h, pos[:, None], sliding)
            k, v = self.keys_values(p, h, pos[:, None], sliding)
            counts = {}
            if sliding:
                keys, values, out = sliding_decode_attention(
                    keys, values, pos, live[:, 0], q, k, v,
                    p.get("sink") if self.has_sinks(True) else None,
                    self.softmax_scale(True))
            else:
                keys, values, out = paged_decode_attention(
                    keys, values, tables, pos, q, k, v)
            x = x + self.output(p, out)
            y, walk = self.feed_forward(p, rms_norm(x, p["ffn_norm"], eps)[:, 0],
                                        live[:, 0])
            if walk is not None:
                counts["walk"] = walk
            return keys, values, x + y[:, None], counts

        def step(params, li, x, pools, tables, pos, live):
            first, second = names[self.is_sliding(li)]
            keys, values, x, counts = step_layer(
                params["layers"][li], x, pools[first], pools[second], tables,
                pos, live, self.is_sliding(li))
            if li == 0:
                # every sliding layer of a step reads the same live slots at
                # the same positions: the live slots and their positions,
                # one array to read; the host scales them by the layers
                counts["sliding"] = jnp.stack([
                    jnp.sum(live, dtype=jnp.int32),
                    jnp.sum(jnp.where(live[:, 0], pos + 1, 0),
                            dtype=jnp.int32)])
            return {first: keys, second: values}, x, counts

        def head(params, x, at=None):
            return final_head(params, x, eps, at)

        def instruments(registry):
            counter = registry.counter
            return dict(
                moe_instruments(registry),
                sliding_read=counter(
                    "serving_sliding_positions_read_total",
                    help="per decode step, live slot and sliding-window "
                         "layer: the key positions the step's read of the "
                         "slot's ring covers (the window)"),
                sliding_live=counter(
                    "serving_sliding_positions_live_total",
                    help="the same, as a read of the whole context would "
                         "cover them: pos + 1"),
                pairs=counter(
                    "serving_prefill_attn_pairs_total",
                    help="query-key pairs that the prefills' attention "
                         "scores, as its blocks lay them, over the layers"),
                causal=counter(
                    "serving_prefill_attn_causal_pairs_total",
                    help="what a dense causal attention over each prefill's "
                         "padded width W would score: layers x W(W+1)/2"))

        def observe(instruments, aux, rows, step):
            walks = [a["walk"] for a in aux if "walk" in a]
            if walks:
                counts, tiles, touched = map(np.stack, zip(*walks))
                observe_walk(instruments, counts, tiles, touched,
                             rows * self.num_experts_per_tok * len(counts),
                             step)
            if step:
                slots, live = map(int, aux[0]["sliding"])
                instruments["sliding_read"].inc(
                    len(sliding_layers) * slots * window)
                instruments["sliding_live"].inc(len(sliding_layers) * live)
            else:
                width = int(aux[0]["width"])
                instruments["pairs"].inc(sum(
                    attention_pairs(width, window if self.is_sliding(i)
                                    else None) for i in layers))
                instruments["causal"].inc(
                    len(layers) * width * (width + 1) // 2)

        return DecodeSpec(
            state=tuple(state), weights=params, num_layers=self.num_hidden_layers,
            max_len=int(self.max_len), vocab_size=int(self.vocab_size),
            geometry=geometry_of(self),
            embed=embed_tokens, prefill=prefill, step=step, head=head,
            instruments=instruments, observe=observe,
            layer_kinds=tuple(names[self.is_sliding(i)] for i in layers),
            sliding=tuple((name, window) for name in names[True])
            if sliding_layers else ())
