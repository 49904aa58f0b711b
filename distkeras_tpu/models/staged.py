"""Pipeline-staged transformer: the model half of pipeline parallelism.

The reference has no pipeline parallelism (its only strategy is socket
parameter-server data parallelism — SURVEY.md §2 parallelism census); this is
a beyond-reference strategy in the same spirit as the GSPMD tensor-parallel
engine.  The TPU-idiomatic formulation (scaling-book pipelining chapter): a
stack of **homogeneous** transformer blocks is split into ``num_stages``
stages of ``blocks_per_stage`` blocks each, block parameters are *stacked*
along a leading ``[num_stages]`` axis so they shard cleanly over a ``stages``
mesh axis, and microbatches stream through the stages via ``ppermute``
neighbour exchanges (see :mod:`distkeras_tpu.parallel.pipeline`).

The embedding and the classifier head are deliberately *not* staged: they
stay replicated and are computed by every stage device (masked into the
pipeline on stage 0 / the last stage).  When they are NOT small next to the
block stack — vocab-scale LM embeddings and heads — ``PipelineEngine(...,
fsdp=True)`` stores them (and their optimizer state) sharded 1/num_stages
per device and all-gathers at use (:mod:`distkeras_tpu.parallel.pipeline`),
trajectory-identical to the replicated layout.

``StagedTransformer`` is a plain :class:`ModelAdapter` whose ``apply`` runs
the stages **sequentially** — the single-device reference semantics used for
initialisation, prediction, and the equivalence tests.  The pipelined
schedule is a different *executor* of the same parameters, not a different
model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.models.adapter import ModelAdapter
from distkeras_tpu.models.transformer import TransformerEncoderBlock

__all__ = ["StagedTransformer", "StagedLM", "stack_block_params"]


def stack_block_params(per_block, num_stages, blocks_per_stage, xp=jnp):
    """Fold a list of per-block param trees into the staged
    ``[num_stages, blocks_per_stage, ...]`` leaf layout — THE contract
    :class:`~distkeras_tpu.parallel.pipeline.PipelineEngine`'s stage
    sharding relies on, kept in one place so init and checkpoint
    conversion (``models/hf_staged.py``) cannot drift.  ``xp=np`` keeps
    converted checkpoints as host leaves (no eager device transfer)."""
    stacked = jax.tree.map(lambda *xs: xp.stack(xs), *per_block)
    return jax.tree.map(
        lambda x: x.reshape((num_stages, blocks_per_stage) + x.shape[1:]),
        stacked,
    )


class _Embed(nn.Module):
    vocab_size: int
    dim: int
    max_len: int

    @nn.compact
    def __call__(self, tokens, offset=0, positions=None):
        tokens = tokens.astype(jnp.int32)
        x = nn.Embed(self.vocab_size, self.dim, name="tok_embed")(tokens)
        pos_embed = nn.Embed(self.max_len, self.dim, name="pos_embed")
        if positions is not None:
            # sequence packing: batched [b, width] per-segment positions
            return x + pos_embed(positions)
        return x + pos_embed(offset + jnp.arange(tokens.shape[1]))[None]


class _Head(nn.Module):
    num_classes: int
    ln_eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(epsilon=self.ln_eps)(x)
        token_logits = nn.Dense(self.num_classes, name="out")(x)
        return token_logits.sum(axis=1) / x.shape[1]


class _LMHead(nn.Module):
    vocab_size: int
    ln_eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(epsilon=self.ln_eps)(x)
        return nn.Dense(self.vocab_size, name="out")(x)  # [b, seq, vocab]


@dataclasses.dataclass
class StagedTransformer(ModelAdapter):
    """Token classifier over ``[batch, seq]`` int32 inputs with its encoder
    blocks stacked ``[num_stages, blocks_per_stage, ...]`` for pipelining.

    Parameter layout (the contract :class:`~distkeras_tpu.parallel.pipeline.
    PipelineEngine` relies on)::

        {"embed": <replicated>, "blocks": <leaves [S, per_stage, ...]>,
         "head": <replicated>}
    """

    vocab_size: int
    num_classes: int = 2
    dim: int = 128
    heads: int = 4
    num_stages: int = 2
    blocks_per_stage: int = 1
    max_len: int = 2048
    ln_eps: float = 1e-6  # 1e-5 for GPT-2 checkpoints (models/hf_staged.py)
    #: set to the seq mesh axis name for pipeline x sequence parallelism:
    #: blocks run ring attention over it and the engine shards tokens/labels
    #: along it (PipelineEngine(seq_shards=k)); decode needs a seq_axis=None
    #: twin — `dataclasses.replace(model, seq_axis=None)`, same params
    seq_axis: Optional[str] = None
    outputs_logits: bool = True

    def __post_init__(self):
        self._embed = _Embed(self.vocab_size, self.dim, self.max_len)
        self._block = self._make_block()
        self._head = self._make_head()

    def _make_block(self):
        return TransformerEncoderBlock(self.dim, self.heads,
                                       seq_axis=self.seq_axis,
                                       ln_eps=self.ln_eps)

    def _make_head(self):
        return _Head(self.num_classes, ln_eps=self.ln_eps)

    # ------------------------------------------------------------------ init
    def init(self, rng: jax.Array, sample_input) -> Tuple[Any, Any]:
        sample = jnp.asarray(sample_input)
        r_embed, r_blocks, r_head = jax.random.split(rng, 3)
        embed_p = self._embed.init(r_embed, sample)["params"]
        h = self._embed.apply({"params": embed_p}, sample)
        n_blocks = self.num_stages * self.blocks_per_stage
        # homogeneous blocks: init each with its own key, stack the pytrees,
        # then fold the flat [n_blocks] axis into [S, per_stage]
        block_ps = [
            self._block.init(jax.random.fold_in(r_blocks, i), h)["params"]
            for i in range(n_blocks)
        ]
        stacked = stack_block_params(
            block_ps, self.num_stages, self.blocks_per_stage
        )
        head_p = self._head.init(r_head, h)["params"]
        return {"embed": embed_p, "blocks": stacked, "head": head_p}, {}

    # ------------------------------------------------- stage pieces (public
    # to the pipeline engine; all pure functions of explicit params)
    def embed(self, embed_params, tokens, offset=0, positions=None):
        return self._embed.apply({"params": embed_params}, tokens, offset,
                                 positions)

    def stage(self, stage_params, h, segment_ids=None):
        """Apply one stage: scan ``blocks_per_stage`` blocks whose param
        leaves carry a leading ``[blocks_per_stage]`` axis.  ``segment_ids``
        (sequence packing) threads to every block's attention mask."""

        def body(x, p):
            return self._block.apply(
                {"params": p}, x, segment_ids=segment_ids), None

        h, _ = lax.scan(body, h, stage_params)
        return h

    def head(self, head_params, h):
        return self._head.apply({"params": head_params}, h)

    # ----------------------------------------------------------- sequential
    def apply(self, params, state, inputs, training=False, rng=None):
        h = self.embed(params["embed"], inputs)

        def body(x, p):
            return self.stage(p, x), None

        h, _ = lax.scan(body, h, params["blocks"])
        return self.head(params["head"], h), state


@dataclasses.dataclass
class StagedLM(StagedTransformer):
    """Pipeline-staged causal language model: the GPipe-for-LM shape.

    Same staged layout as :class:`StagedTransformer` (embed replicated,
    homogeneous block stages stacked ``[S, per_stage, ...]``, head
    replicated) with causal blocks and a per-token vocab head — trained
    with ``loss="token_crossentropy"``; the engines shard the integer
    label array like the tokens (``per_token_labels``).  Output width is
    ``vocab_size``; the inherited ``num_classes`` field does not apply.

    ``packed=True`` consumes sequence-packed ``[batch, width, 2]`` input
    (token + segment-ID channels, :meth:`PackedBatch.model_inputs`) through
    the *sequential* executor: per-segment positions, intra-segment
    attention masks, train with ``loss="masked_token_crossentropy"``.
    The pipeline schedule (``pipeline_stages>1``) does not thread segment
    IDs — train packed StagedLMs on the windowed/GSPMD engines.
    """

    per_token_labels: bool = True
    packed: bool = False

    def __post_init__(self):
        if self.num_classes != type(self).num_classes:
            raise ValueError(
                "StagedLM outputs vocab_size-wide logits; num_classes does "
                "not apply — did you mean StagedTransformer?"
            )
        super().__post_init__()

    def _make_block(self):
        # max_len sizes the per-block KV cache for decode (training ignores
        # it); with seq_axis set, attention is CAUSAL RING attention and
        # decode requires the seq_axis=None twin (see StagedTransformer)
        return TransformerEncoderBlock(self.dim, self.heads, causal=True,
                                       max_len=self.max_len,
                                       seq_axis=self.seq_axis,
                                       ln_eps=self.ln_eps)

    def _make_head(self):
        return _LMHead(self.vocab_size, ln_eps=self.ln_eps)

    # ------------------------------------------------------------------ init
    def init(self, rng: jax.Array, sample_input) -> Tuple[Any, Any]:
        if self.packed:
            # init on the token channel: the packed and unpacked executors
            # share one param tree (the parity test swaps params between them)
            sample_input = jnp.asarray(sample_input)[..., 0]
        return super().init(rng, sample_input)

    # ----------------------------------------------------------- sequential
    def apply(self, params, state, inputs, training=False, rng=None):
        if not self.packed:
            return super().apply(params, state, inputs, training, rng)
        if self.seq_axis is not None:
            raise ValueError(
                "packed=True is incompatible with seq_axis (ring attention "
                "has no segment-mask block structure)"
            )
        from distkeras_tpu.models.transformer import packed_positions

        tokens = inputs[..., 0]
        segment_ids = inputs[..., 1].astype(jnp.int32)
        h = self.embed(params["embed"], tokens,
                       positions=packed_positions(segment_ids))

        def body(x, p):
            return self.stage(p, x, segment_ids=segment_ids), None

        h, _ = lax.scan(body, h, params["blocks"])
        return self.head(params["head"], h), state

    # ------------------------------------------------------- KV-cache decode
    def init_cache(self, batch_size: int, dtype=jnp.float32):
        """Zeroed per-block KV caches, stacked ``[n_blocks, ...]`` to scan
        with the flat block stack in :meth:`decode_step`."""
        dummy = jnp.zeros((batch_size, 1, self.dim), dtype)
        shapes = jax.eval_shape(
            lambda: self._block.init(jax.random.PRNGKey(0), dummy, decode=True)
        )["cache"]
        n_blocks = self.num_stages * self.blocks_per_stage
        return jax.tree.map(
            lambda s: jnp.zeros((n_blocks,) + s.shape, s.dtype), shapes
        )

    def decode_step(self, params, cache, tokens, pos_offset):
        """Run one decode chunk (prompt at prefill, 1 token per generation
        step) through the *sequential* stage stack with per-block KV caches:
        returns ``(logits [b, chunk, vocab], new_cache)``.  Same math as the
        full-context ``apply`` on the prefix (tests/test_generate.py); like
        prediction, generation runs on the plain sequential executor — the
        pipeline is a training-time schedule."""
        h = self.embed(params["embed"], tokens, offset=pos_offset)
        flat_blocks = jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), params["blocks"]
        )

        def body(x, block):
            p, c = block
            y, upd = self._block.apply(
                {"params": p, "cache": c}, x, decode=True, mutable=["cache"]
            )
            return y, upd["cache"]

        h, new_cache = lax.scan(body, h, (flat_blocks, cache))
        return self.head(params["head"], h), new_cache

    def decode_spec(self, params):
        """What the serving engine serves this model by
        (:class:`distkeras_tpu.models.decode.DecodeSpec`): the
        ``[S, per_stage, ...]`` block stack unfolds into a flat per-block
        list (same order as :meth:`decode_step`'s scan); embed/head are
        already replicated.  Like prediction, serving runs the sequential
        executor — the pipeline is a training-time schedule."""
        from distkeras_tpu.models.decode import transformer_decode_spec

        if self.seq_axis is not None:
            raise ValueError(
                "serving decodes on the single-device twin — build the "
                "engine from a seq_axis=None replica "
                "(dataclasses.replace(model, seq_axis=None), same params)"
            )
        flat = jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), params["blocks"]
        )
        n_blocks = self.num_stages * self.blocks_per_stage
        return transformer_decode_spec(
            tok=params["embed"]["tok_embed"]["embedding"],
            pos=params["embed"]["pos_embed"]["embedding"],
            blocks=[jax.tree.map(lambda x, i=i: x[i], flat)
                    for i in range(n_blocks)],
            final_ln=params["head"]["LayerNorm_0"], head=params["head"]["out"],
            dim=self.dim, heads=self.heads, head_dim=self.dim // self.heads,
            max_len=self.max_len, vocab_size=self.vocab_size,
            ln_eps=self.ln_eps,
        )
