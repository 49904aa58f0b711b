"""Transformer models with optional sequence parallelism.

Beyond the reference's model scale (SURVEY.md §5.7): a Transformer encoder
classifier whose sequence axis can be sharded over a mesh axis.  When
``seq_axis`` is set (running inside ``shard_map`` with that axis), attention
runs as ring attention (:mod:`distkeras_tpu.parallel.ring`) and the classifier
head pools *per-token logits* so every parameter-consuming op sees sharded
activations — which makes the cross-shard gradient sync a plain ``psum`` over
the sequence axis (done by the engine), with no replicated-activation
double-counting.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax

from distkeras_tpu.utils.compat import axis_size
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.parallel.ring import attention, ring_attention

__all__ = ["TransformerClassifier", "TransformerEncoderBlock", "TransformerLM",
           "packed_positions"]


def packed_positions(segment_ids):
    """Per-segment positions ``[batch, width]`` from packed segment IDs
    (:func:`distkeras_tpu.datapipe.pack_sequences` convention: monotone
    per-row, 0 = pad): each token's index minus the index of its segment's
    first token, so every segment sees the positions ``0..len-1`` a
    standalone sequence would — computed on device with a cummax over
    segment starts (no host round-trip, no python loop)."""
    segment_ids = jnp.asarray(segment_ids)
    idx = jnp.arange(segment_ids.shape[1], dtype=jnp.int32)
    prev = jnp.concatenate(
        [jnp.full_like(segment_ids[:, :1], -1), segment_ids[:, :-1]], axis=1
    )
    is_start = segment_ids != prev
    start = lax.cummax(jnp.where(is_start, idx[None], 0), axis=1)
    return idx[None] - start


class _SelfAttention(nn.Module):
    dim: int
    heads: int
    seq_axis: Optional[str] = None
    causal: bool = False
    max_len: Optional[int] = None  # KV-cache capacity for decode mode

    @nn.compact
    def __call__(self, x, training: bool = False, decode: bool = False,
                 segment_ids=None):
        head_dim = self.dim // self.heads
        qkv = nn.DenseGeneral((3, self.heads, head_dim), name="qkv")(x)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        if decode:
            if segment_ids is not None:
                raise ValueError(
                    "segment_ids (sequence packing) is a training-path "
                    "feature; decode serves one sequence per row"
                )
            out = self._decode_attention(q, k, v)
        elif self.seq_axis is not None:
            if segment_ids is not None:
                raise ValueError(
                    "segment_ids is incompatible with seq_axis: ring "
                    "attention has no segment-mask block structure — pack "
                    "with seq_axis=None"
                )
            out = ring_attention(q, k, v, self.seq_axis, causal=self.causal)
        else:
            out = attention(q, k, v, causal=self.causal,
                            segment_ids=segment_ids)
        return nn.DenseGeneral(self.dim, axis=(-2, -1), name="proj")(out)

    def _decode_attention(self, q, k, v):
        """Chunked KV-cache attention for autoregressive decode: append this
        chunk's K/V at the cache cursor, attend the chunk's queries over the
        whole (padded) cache with position masking.  One code path serves
        prefill (chunk = prompt) and generation (chunk = 1 token); padded
        cache rows mask to exp(-inf) = 0 exactly, so the math matches the
        full-context recompute path (tests/test_generate.py).  Cache
        variables materialise on first use — run the prefill chunk with
        ``mutable=["cache"]`` and no separate cache-init call is needed."""
        if not self.causal or self.seq_axis is not None or self.max_len is None:
            raise ValueError(
                "KV-cache decode needs causal=True, seq_axis=None and "
                "max_len set (generation runs on the single-device twin)"
            )
        b, chunk, h, hd = q.shape
        cap = self.max_len
        ck = self.variable("cache", "cached_key", jnp.zeros, (b, cap, h, hd), k.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros, (b, cap, h, hd), v.dtype)
        idx = self.variable("cache", "cache_index",
                            lambda: jnp.zeros((), jnp.int32))
        i = idx.value
        ck.value = lax.dynamic_update_slice(ck.value, k, (0, i, 0, 0))
        cv.value = lax.dynamic_update_slice(cv.value, v, (0, i, 0, 0))
        idx.value = i + chunk
        # same layout/scale as ring.local_attention's reference math
        qt = jnp.moveaxis(q, 1, 2)                 # [b, h, chunk, hd]
        kt = jnp.moveaxis(ck.value, 1, 2)          # [b, h, cap, hd]
        vt = jnp.moveaxis(cv.value, 1, 2)
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
        q_pos = (i + jnp.arange(chunk))[:, None]   # [chunk, 1]
        key_pos = jnp.arange(cap)[None, :]         # [1, cap]
        s = jnp.where(key_pos <= q_pos, s, -jnp.inf)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vt)
        # Decoding past max_len would silently clamp the cache write and
        # attend over corrupted rows; the cursor is traced so we cannot
        # raise — poison the output with NaN instead, which no plausible
        # generation survives unnoticed.  (greedy_generate validates
        # prompt+steps <= max_len statically and never hits this.)
        out = jnp.where(i + chunk > cap, jnp.nan, out)
        return jnp.moveaxis(out, 1, 2)


class TransformerEncoderBlock(nn.Module):
    dim: int
    heads: int
    mlp_ratio: int = 4
    seq_axis: Optional[str] = None
    causal: bool = False
    dropout: float = 0.0
    max_len: Optional[int] = None  # KV-cache capacity (decode mode only)
    ln_eps: float = 1e-6  # GPT-2 checkpoints use 1e-5 (models/hf_staged.py)

    @nn.compact
    def __call__(self, x, training: bool = False, decode: bool = False,
                 segment_ids=None):
        h = nn.LayerNorm(epsilon=self.ln_eps)(x)
        h = _SelfAttention(self.dim, self.heads, self.seq_axis, self.causal,
                           self.max_len)(h, training, decode,
                                         segment_ids=segment_ids)
        if self.dropout > 0:
            h = nn.Dropout(self.dropout, deterministic=not training)(h)
        x = x + h
        h = nn.LayerNorm(epsilon=self.ln_eps)(x)
        h = nn.Dense(self.dim * self.mlp_ratio)(h)
        h = nn.gelu(h)
        h = nn.Dense(self.dim)(h)
        if self.dropout > 0:
            h = nn.Dropout(self.dropout, deterministic=not training)(h)
        return x + h


def _encode_tokens(tokens, *, vocab_size, dim, heads, num_layers, max_len,
                   seq_axis, causal, dropout, training, decode=False,
                   pos_offset=None, positions=None, segment_ids=None):
    """Shared classifier/LM trunk: token + (block-offset) positional
    embeddings, encoder-block stack, final LayerNorm.  Must be called from
    inside an ``@nn.compact`` ``__call__`` — the modules it instantiates
    attach to the caller's scope (flat param names).

    ``positions`` (``[batch, width]``, sequence packing) overrides the
    arange-derived positions with per-segment ones; ``segment_ids`` threads
    down to every block's attention mask."""
    tokens = tokens.astype(jnp.int32)
    block_len = tokens.shape[1]
    x = nn.Embed(vocab_size, dim, name="tok_embed")(tokens)
    pos_embed = nn.Embed(max_len, dim, name="pos_embed")
    if positions is not None:
        x = x + pos_embed(positions)
    else:
        if pos_offset is not None:
            offset = pos_offset
        else:
            offset = lax.axis_index(seq_axis) * block_len if seq_axis is not None else 0
        x = x + pos_embed(offset + jnp.arange(block_len))[None]
    for i in range(num_layers):
        x = TransformerEncoderBlock(
            dim, heads, seq_axis=seq_axis, causal=causal,
            dropout=dropout, max_len=max_len, name=f"block_{i}",
        )(x, training, decode, segment_ids=segment_ids)
    return nn.LayerNorm()(x)


class TransformerLM(nn.Module):
    """Causal language model over ``[batch, seq(block)]`` int32 tokens,
    emitting per-token next-token logits ``[batch, seq(block), vocab]``.

    Long-context first-class: with ``seq_axis`` set (inside ``shard_map``
    over that axis), attention runs as *causal ring attention* — each
    device holds one sequence block, K/V blocks rotate around the ring —
    and the per-token logits (and their integer labels, sharded by the
    engine) stay block-local, so memory per device is O(seq/shards).
    Train with ``loss="token_crossentropy"`` /
    ``metrics=("token_accuracy",)``.

    ``packed=True`` consumes sequence-packed input
    (:func:`distkeras_tpu.datapipe.pack_sequences`): ``[batch, width, 2]``
    int32 with token and segment-ID channels
    (:meth:`PackedBatch.model_inputs`).  Positions restart per segment and
    attention is masked intra-segment, so each packed segment's logits
    equal the logits the sequence would get alone in a row
    (tests/test_datapipe.py pins this).  Train packed models with
    ``loss="masked_token_crossentropy"`` — the packer marks pads and
    segment tails with ``-1`` labels.
    """

    vocab_size: int
    dim: int = 128
    heads: int = 4
    num_layers: int = 2
    max_len: int = 2048
    seq_axis: Optional[str] = None
    dropout: float = 0.0
    packed: bool = False

    #: engines shard the label array like the token array (per-token labels)
    per_token_labels = True

    @nn.compact
    def __call__(self, tokens, training: bool = False, decode: bool = False):
        pos_offset = None
        positions = None
        segment_ids = None
        if self.packed:
            if decode:
                raise ValueError(
                    "packed=True is a training-path layout; decode with a "
                    "packed=False twin (same params)"
                )
            if self.seq_axis is not None:
                raise ValueError(
                    "packed=True is incompatible with seq_axis (ring "
                    "attention has no segment-mask block structure)"
                )
            tokens, segment_ids = tokens[..., 0], tokens[..., 1]
            segment_ids = segment_ids.astype(jnp.int32)
            positions = packed_positions(segment_ids)
        if decode:
            # decode chunks carry no absolute positions; a top-level cache
            # cursor supplies them (prefill advances it by the prompt length,
            # each generation step by 1)
            pi = self.variable("cache", "pos_index",
                               lambda: jnp.zeros((), jnp.int32))
            pos_offset = pi.value
            pi.value = pos_offset + tokens.shape[1]
        x = _encode_tokens(
            tokens, vocab_size=self.vocab_size, dim=self.dim, heads=self.heads,
            num_layers=self.num_layers, max_len=self.max_len,
            seq_axis=self.seq_axis, causal=True, dropout=self.dropout,
            training=training, decode=decode, pos_offset=pos_offset,
            positions=positions, segment_ids=segment_ids,
        )
        return nn.Dense(self.vocab_size, name="lm_head")(x)

    def decode_spec(self, params):
        """What the serving engine serves this model by
        (:class:`distkeras_tpu.models.decode.DecodeSpec`): the block's two
        pools a layer and its embedding, prefill, step and head over this
        module's own param tree.  Kept next to the model so the serving
        layer cannot drift from the param tree this module actually
        builds."""
        from distkeras_tpu.models.decode import transformer_decode_spec

        if self.seq_axis is not None:
            raise ValueError(
                "serving decodes on the single-device twin — build the "
                "engine from a seq_axis=None model with the same params"
            )
        return transformer_decode_spec(
            tok=params["tok_embed"]["embedding"],
            pos=params["pos_embed"]["embedding"],
            blocks=[params[f"block_{i}"] for i in range(self.num_layers)],
            final_ln=params["LayerNorm_0"], head=params["lm_head"],
            dim=self.dim, heads=self.heads,
            # explicit head geometry: the tensor-parallel build shards the
            # qkv kernels over heads, so the global count must come from
            # here, not from (shard-local) kernel shapes
            head_dim=self.dim // self.heads, max_len=self.max_len,
            vocab_size=self.vocab_size,
            # blocks and the final LayerNorm both use the flax default
            ln_eps=1e-6,
        )


class TransformerClassifier(nn.Module):
    """Token classifier over [batch, seq(block)] int32 inputs.

    With ``seq_axis`` set, the input is this device's sequence *block*;
    positional embeddings are offset by the block index and the head output
    is psum-pooled over the axis (replicated logits out).
    """

    vocab_size: int
    num_classes: int = 2
    dim: int = 128
    heads: int = 4
    num_layers: int = 2
    max_len: int = 2048
    seq_axis: Optional[str] = None
    causal: bool = False
    dropout: float = 0.0

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        block_len = tokens.shape[1]
        seq_total = (
            block_len * axis_size(self.seq_axis)
            if self.seq_axis is not None else block_len
        )
        x = _encode_tokens(
            tokens, vocab_size=self.vocab_size, dim=self.dim, heads=self.heads,
            num_layers=self.num_layers, max_len=self.max_len,
            seq_axis=self.seq_axis, causal=self.causal, dropout=self.dropout,
            training=training,
        )
        token_logits = nn.Dense(self.num_classes, name="head")(x)  # [b, blk, C]
        logits = token_logits.sum(axis=1) / seq_total
        if self.seq_axis is not None:
            logits = lax.psum(logits, self.seq_axis)
        return logits
