"""A causal LM of **Mamba and attention layers**: Jamba's block.

The block of AI21's Jamba family (``jamba``): most layers are Mamba-1
state-space mixers, whose state has a fixed size a sequence, and every
``attn_layer_period`` layers one grouped-query attention layer; every layer
then a dense gated feed-forward (``num_experts`` 1: no experts).  No
positional encoding anywhere: position comes from the Mamba layers.

Equations (``x`` a layer's input, ``norm`` RMSNorm with a learned weight at
``rms_norm_eps``):

* layer ``i`` is attention iff ``i % attn_layer_period ==
  attn_layer_offset``, else Mamba: ``x = x + mixer(norm(x)); x = x +
  ffn(norm(x))``; a final ``norm`` and the head the embedding transposed
  (``tie_word_embeddings``);
* attention: ``q = h W_q [heads, head_dim]``, ``k = h W_k``, ``v = h W_v``
  ``[kv_heads, head_dim]`` (``head_dim = hidden / heads``), no bias, no
  rotary; key-value head ``i`` serves query heads ``i x group .. (i + 1) x
  group - 1``; causal softmax of ``q . k / sqrt(head_dim)`` in float32;
  ``o = W_o sum_j p_j v_j``;
* Mamba (``d_inner = mamba_expand x hidden``, ``n = mamba_d_state``, ``r =
  mamba_dt_rank``, ``K = mamba_d_conv``), for the normed input ``u``:
  ``[x; z] = W_in u``; ``x~_t = silu(sum_k w[k] x_{t - K + 1 + k} + b)``
  (depthwise and causal, positions before 0 are 0); ``[delta; B; C] = W_x
  x~``, each RMS-normed with its own weight; ``Delta = softplus(W_dt delta
  + b_dt)``; ``A = -exp(A_log) [n, d_inner]``; the selective scan ``h_t =
  exp(Delta_t A) h_{t-1} + Delta_t B_t x~_t``, ``y_t = C_t . h_t + D x~_t``
  (:mod:`distkeras_tpu.ops.pallas.selective_scan`); out ``W_out (y *
  silu(z))``;
* feed-forward: ``W_down(silu(W_gate h) * W_up h)``.

**Serving** (``decode_spec``): an attention layer keeps ``k`` and ``v`` rows
of its key-value heads for every position, paged through the slots' tables
and read by :func:`~distkeras_tpu.serving.cache.paged_decode_attention`; a
Mamba layer keeps two **recurrent** states a slot (``DecodeSpec.
recurrent``): ``ssm``, the scan state ``[n, d_inner]`` in float32 (it runs
over thousands of steps: the published Mamba kernels hold it in float32 too,
``DecodeSpec.dtypes``), and ``conv``, the last ``K - 1`` inputs of the
convolution in the pools' type.  A prefill chunk runs the whole chunk
through the scan from ``h = 0`` with ``Delta`` zeroed on the rows past the
prompt, so that the padding leaves the state as the prompt left it, and
hands over the state and the prompt's last ``K - 1`` inputs (zeros before
position 0); the step advances the convolution's tail and the scan state of
the live slots and leaves the others as they were.  The block counts the
positions its prefills scanned (``serving_ssm_scan_positions_total``, the
chunk's static width over the Mamba layers).  No ``window`` (speculative
verify) and no ``shard`` (tensor-parallel) twin: the engine refuses
``draft_model=`` and ``mesh=`` for this block.

Precision as in the other blocks of this package: products take their
operands in the weights' type and accumulate in float32; residual stream,
norms, softmax, the convolution's sums, ``Delta``, its exponential, the scan
state and the ``C`` contraction float32; the convolution's inputs are kept
in the weights' type, in the prefill and in the step alike.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.latent_moe import (
    F32, _dot, embed_tokens, gated, geometry_of, init_params, rms_norm)
from distkeras_tpu.models.window_moe import block_attention, query_blocks

__all__ = ["JambaLM"]

#: the step's range of Mamba's initialisation (``dt_min``, ``dt_max``)
DT_RANGE = (0.001, 0.1)


def mamba_a_log(d_state, d_inner):
    """Mamba's own ``A_log``: ``log(1 .. d_state)`` for every channel, laid
    out as the state, ``[d_state, d_inner]`` float32."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, d_state + 1, dtype=F32))[:, None],
        (d_state, d_inner))


def mamba_dt_bias(key, d_inner):
    """Mamba's own ``dt_proj`` bias: the inverse softplus of steps drawn
    log-uniform in :data:`DT_RANGE`, float32."""
    low, high = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
    dt = jnp.exp(jax.random.uniform(key, (d_inner,), F32) * (high - low) + low)
    return dt + jnp.log(-jnp.expm1(-dt))


def tied_head(params, x, eps, at=None):
    """The final norm and the head, the embedding transposed, over ``x
    [batch, rows, dim]``; with ``at`` one row of a ``[1, width, dim]`` chunk
    alone."""
    if at is not None:
        x = jax.lax.dynamic_index_in_dim(x[0], at, axis=0, keepdims=False)
    return _dot(rms_norm(x, params["norm"], eps), params["embed"],
                "...d,vd->...v")


@dataclasses.dataclass(frozen=True, eq=False)
class JambaLM:
    """See the module docstring.  Field names follow the family's published
    ``config.json``; ``max_len`` is the served window (there is no position
    table).  The published choices alone are built: a convolution with a
    bias, projections without, a tied head, no experts."""

    vocab_size: int
    max_len: int
    hidden_size: int = 2560
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    intermediate_size: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if not (self.mamba_conv_bias and not self.mamba_proj_bias
                and self.tie_word_embeddings):
            raise ValueError("JambaLM builds the published choices alone: "
                             "mamba_conv_bias, no mamba_proj_bias, a tied head")
        if self.d_inner > 128 and self.d_inner % 128:
            raise ValueError(f"d_inner {self.d_inner}: a store's row is the "
                             "channels, at most 128 or whole lanes of 128")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the key-value heads must divide the heads")

    # ------------------------------------------------------------- sizes

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_attention(self, index: int) -> bool:
        return index % self.attn_layer_period == self.attn_layer_offset

    @property
    def mamba_layers(self) -> int:
        return sum(not self.is_attention(i)
                   for i in range(self.num_hidden_layers))

    # ----------------------------------------------------------- weights

    def param_shapes(self):
        """``{path: (shape, fan_in)}``; ``fan_in`` None marks a norm's weight
        (ones) and 0 a bias drawn small; ``A_log``, ``dt_bias`` and ``D`` are
        set by :meth:`init` by Mamba's own rule."""
        d, di, n = self.hidden_size, self.d_inner, self.mamba_d_state
        r, heads, kv = (self.mamba_dt_rank, self.num_attention_heads,
                        self.num_key_value_heads)
        hd, ff = self.head_dim, self.intermediate_size
        shapes = {("embed",): ((self.vocab_size, d), d),
                  ("norm",): ((d,), None)}
        for i in range(self.num_hidden_layers):
            layer = {"input_norm": ((d,), None), "ffn_norm": ((d,), None),
                     "gate": ((d, ff), d), "up": ((d, ff), d),
                     "down": ((ff, d), ff)}
            if self.is_attention(i):
                layer.update({"q": ((d, heads, hd), d),
                              "k": ((d, kv, hd), d), "v": ((d, kv, hd), d),
                              "o": ((heads, hd, d), heads * hd)})
            else:
                layer.update({
                    "in_proj": ((d, 2 * di), d),
                    "conv_w": ((self.mamba_d_conv, di), self.mamba_d_conv),
                    "conv_b": ((di,), 0),
                    "x_proj": ((di, r + 2 * n), di),
                    "dt_norm": ((r,), None), "b_norm": ((n,), None),
                    "c_norm": ((n,), None),
                    "dt_proj": ((r, di), r), "dt_bias": ((di,), 0),
                    "A_log": ((n, di), 0), "D": ((di,), None),
                    "out_proj": ((di, d), di)})
            for name, entry in layer.items():
                shapes[("layers", i, name)] = entry
        return shapes

    def init(self, key, dtype=jnp.float32):
        """A parameter tree from ``key``: matrices normal at ``1 / sqrt(fan
        in)`` in ``dtype``, norms' weights and ``D`` one, the convolution's
        bias small, ``A_log`` and ``dt_bias`` by Mamba's own rule, all
        vectors float32."""
        tree = {"layers": [{} for _ in range(self.num_hidden_layers)]}
        tree = init_params(tree, self.param_shapes(), key, dtype, 0.02)
        for i, layer in enumerate(tree["layers"]):
            if "A_log" in layer:
                layer["A_log"] = mamba_a_log(self.mamba_d_state, self.d_inner)
                layer["dt_bias"] = mamba_dt_bias(
                    jax.random.fold_in(key, 1_000_003 + i), self.d_inner)
        return tree

    # -------------------------------------------------------------- Mamba

    def mamba_inputs(self, p, h):
        """``(x, z)`` of the normed input ``h [rows, dim]``: the
        convolution's input in the weights' type, the gate float32."""
        xz = _dot(h, p["in_proj"], "td,de->te")
        return xz[:, :self.d_inner].astype(p["in_proj"].dtype), xz[
            :, self.d_inner:]

    @staticmethod
    def convolve(p, window):
        """``silu`` of the causal depthwise convolution at the last of
        ``window [..., K, d_inner]`` (the K inputs up to that position),
        float32."""
        w = p["conv_w"].astype(F32)
        total = p["conv_b"].astype(F32)
        for k in range(w.shape[0]):
            total = total + w[k] * window[..., k, :].astype(F32)
        return jax.nn.silu(total)

    def convolve_chunk(self, p, x):
        """The convolution over a chunk ``x [rows, d_inner]`` that starts at
        position 0: zeros before it."""
        k = self.mamba_d_conv
        padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
        rows = x.shape[0]
        return self.convolve(p, jnp.stack(
            [padded[i:i + rows] for i in range(k)], axis=-2))

    def scan_inputs(self, p, xc):
        """``(Delta, B, C)`` of the convolved stream ``xc [rows,
        d_inner]``, float32."""
        r, n, eps = self.mamba_dt_rank, self.mamba_d_state, self.rms_norm_eps
        dbc = _dot(xc, p["x_proj"], "tc,ce->te")
        dt = rms_norm(dbc[:, :r], p["dt_norm"], eps)
        b = rms_norm(dbc[:, r:r + n], p["b_norm"], eps)
        c = rms_norm(dbc[:, r + n:], p["c_norm"], eps)
        delta = jax.nn.softplus(_dot(dt, p["dt_proj"], "tr,rc->tc")
                                + p["dt_bias"].astype(F32))
        return delta, b, c

    @staticmethod
    def decay(p):
        """``A = -exp(A_log)``, ``[d_state, d_inner]`` float32."""
        return -jnp.exp(p["A_log"].astype(F32))

    def mixer_output(self, p, y, z):
        return _dot(y * jax.nn.silu(z), p["out_proj"], "tc,cd->td")

    def mamba_chunk(self, p, h, live):
        """The Mamba mixer over one chunk ``h [rows, dim]`` (normed) from
        ``h = 0``, whose rows ``live [rows]`` are the prompt's: ``(out [rows,
        dim], the scan state after the prompt [d_state, d_inner], the
        convolution's input [rows, d_inner])``.  ``Delta`` is zeroed past
        the prompt, so that the padding leaves the state as it was."""
        from distkeras_tpu.ops.pallas.selective_scan import selective_scan

        x, z = self.mamba_inputs(p, h)
        xc = self.convolve_chunk(p, x)
        delta, b, c = self.scan_inputs(p, xc)
        delta = jnp.where(live[:, None], delta, 0.0)
        y, state = selective_scan(xc, delta, b, c, self.decay(p), p["D"])
        return self.mixer_output(p, y, z), state, x

    # ---------------------------------------------------------- attention

    def chunk_attention(self, p, h):
        """``(attn(h) [1, rows, dim], k, v)`` of a chunk ``h [1, rows, dim]``
        that starts at position 0, a block of queries at a time."""
        kind = p["q"].dtype
        q = _dot(h, p["q"], "brd,dhe->brhe")
        k = _dot(h, p["k"], "brd,dhe->brhe")
        v = _dot(h, p["v"], "brd,dhe->brhe")
        keys, values = k.astype(kind), v.astype(kind)
        out = []
        for start, end, first in query_blocks(h.shape[1]):
            out.append(_dot(block_attention(
                q[:, start:end].astype(kind), keys[:, :end], values[:, :end],
                start, first, self.head_dim ** -0.5), p["o"],
                "brhe,hed->brd"))
        return jnp.concatenate(out, axis=1), k, v

    # ------------------------------------------------------ full forward

    def __call__(self, params, tokens):
        """Next-token logits ``[batch, rows, vocab]`` (float32) of whole
        sequences ``tokens [batch, rows]``: no cache."""
        tokens = jnp.asarray(tokens, jnp.int32)
        eps = self.rms_norm_eps
        x = embed_tokens(params, tokens, None)
        live = jnp.ones(tokens.shape[1], bool)
        for i, p in enumerate(params["layers"]):
            h = rms_norm(x, p["input_norm"], eps)
            if self.is_attention(i):
                x = x + jax.vmap(lambda one: self.chunk_attention(
                    p, one[None])[0][0])(h)
            else:
                x = x + jax.vmap(lambda one: self.mamba_chunk(
                    p, one, live)[0])(h)
            h = rms_norm(x, p["ffn_norm"], eps)
            x = x + gated(h.reshape(-1, h.shape[-1]), p["gate"], p["up"],
                          p["down"]).reshape(x.shape)
        return tied_head(params, x, eps)

    # ------------------------------------------------------------ serving

    def decode_spec(self, params):
        """What the serving engine serves this model by
        (:class:`distkeras_tpu.models.decode.DecodeSpec`): ``k`` and ``v``
        paged in the attention layers, ``ssm`` (float32) and ``conv``
        recurrent in the Mamba layers (``layer_kinds``, ``recurrent``,
        ``dtypes``); the blocked attention and the scan from ``h = 0`` for a
        prefill chunk, the paged grouped-query read and one position of the
        scan for the step.  The block's own counter:
        ``serving_ssm_scan_positions_total``, per prefill the chunk's static
        width over the Mamba layers (handed back in the first layer's counts
        and counted by the host, one program behind)."""
        from distkeras_tpu.models.decode import DecodeSpec
        from distkeras_tpu.ops.pallas.selective_scan import selective_step
        from distkeras_tpu.serving.cache import paged_decode_attention

        eps = self.rms_norm_eps
        di, conv_rows = self.d_inner, self.mamba_d_conv - 1
        kv_width = self.num_key_value_heads * self.head_dim
        layers = range(self.num_hidden_layers)
        attention = [i for i in layers if self.is_attention(i)]
        names = {True: ("k", "v"), False: ("ssm", "conv")}
        state = []
        if attention:
            state += [("k", kv_width), ("v", kv_width)]
        if self.mamba_layers:
            state += [("ssm", di), ("conv", di)]

        def feed_forward(p, x):
            h = rms_norm(x, p["ffn_norm"], eps)
            return x + gated(h.reshape(-1, h.shape[-1]), p["gate"], p["up"],
                             p["down"]).reshape(x.shape)

        # a layer's work is traced and lowered once a kind and shape, not
        # once a layer (XLA inlines the calls)
        @jax.jit
        def prefill_attention(p, x):
            out, k, v = self.chunk_attention(
                p, rms_norm(x, p["input_norm"], eps))
            return feed_forward(p, x + out), k[0], v[0]

        @jax.jit
        def prefill_mamba(p, x, live):
            out, ssm, inputs = self.mamba_chunk(
                p, rms_norm(x, p["input_norm"], eps)[0], live[0])
            # the convolution's tail: the prompt's last K - 1 inputs, zeros
            # before position 0; never a padding row
            at = jnp.sum(live, dtype=jnp.int32) - conv_rows + jnp.arange(
                conv_rows)
            tail = jnp.where((at >= 0)[:, None],
                             inputs[jnp.clip(at, 0, inputs.shape[0] - 1)], 0)
            return feed_forward(p, x + out[None]), ssm, tail

        def prefill(params, li, x, positions, write, live):
            p = params["layers"][li]
            if self.is_attention(li):
                x, k, v = prefill_attention(p, x)
                write("k", k.reshape(k.shape[0], -1))
                write("v", v.reshape(v.shape[0], -1))
            else:
                x, ssm, tail = prefill_mamba(p, x, live)
                write("ssm", ssm)
                write("conv", tail)
            # the chunk's static width, one constant to read: the host counts
            return x, ({"width": jnp.asarray(x.shape[1], jnp.int32)}
                       if li == 0 else None)

        @jax.jit
        def step_attention(p, x, keys, values, tables, pos):
            h = rms_norm(x, p["input_norm"], eps)
            q = _dot(h, p["q"], "brd,dhe->brhe")
            k = _dot(h, p["k"], "brd,dhe->brhe")
            v = _dot(h, p["v"], "brd,dhe->brhe")
            keys, values, out = paged_decode_attention(
                keys, values, tables, pos, q, k, v)
            x = x + _dot(out, p["o"], "brhe,hed->brd")
            return keys, values, feed_forward(p, x)

        @jax.jit
        def step_mamba(p, x, ssm, conv, live):
            slots = x.shape[0]
            x_in, z = self.mamba_inputs(p, rms_norm(x[:, 0], p["input_norm"],
                                                    eps))
            tail = conv[:slots, :, :di]
            window = jnp.concatenate([tail.astype(x_in.dtype),
                                      x_in[:, None]], axis=1)
            xc = self.convolve(p, window)
            kept = jnp.where(live[:, :, None], window[:, 1:], tail)
            conv = conv.at[:slots, :, :di].set(kept.astype(conv.dtype))
            delta, b, c = self.scan_inputs(p, xc)
            ssm, y = selective_step(ssm, xc, delta, b, c, self.decay(p),
                                    p["D"], live[:, 0])
            x = x + self.mixer_output(p, y, z)[:, None]
            return ssm, conv, feed_forward(p, x)

        def step(params, li, x, pools, tables, pos, live):
            p = params["layers"][li]
            if self.is_attention(li):
                keys, values, x = step_attention(p, x, pools["k"], pools["v"],
                                                 tables, pos)
                return {"k": keys, "v": values}, x, None
            ssm, conv, x = step_mamba(p, x, pools["ssm"], pools["conv"], live)
            return {"ssm": ssm, "conv": conv}, x, None

        def head(params, x, at=None):
            return tied_head(params, x, eps, at)

        def instruments(registry):
            return {"scanned": registry.counter(
                "serving_ssm_scan_positions_total",
                help="positions that the prefills' selective scans ran over: "
                     "each chunk's padded width, over the Mamba layers")}

        def observe(instruments, aux, rows, step):
            if not step:
                instruments["scanned"].inc(
                    int(aux[0]["width"]) * self.mamba_layers)

        return DecodeSpec(
            state=tuple(state), weights=params,
            num_layers=self.num_hidden_layers, max_len=int(self.max_len),
            vocab_size=int(self.vocab_size), geometry=geometry_of(self),
            embed=embed_tokens, prefill=prefill, step=step, head=head,
            instruments=instruments, observe=observe,
            layer_kinds=tuple(names[self.is_attention(i)] for i in layers),
            recurrent=((("ssm", self.mamba_d_state), ("conv", conv_rows))
                       if self.mamba_layers else ()),
            dtypes=(("ssm", F32),) if self.mamba_layers else ())
