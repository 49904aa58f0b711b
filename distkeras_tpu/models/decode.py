"""The block contract between a causal LM and the serving engine.

:class:`~distkeras_tpu.serving.engine.ServingEngine` keeps slots, pages,
programs, sampling and the loop; it knows nothing of what a layer computes.
A model's ``decode_spec(params)`` hook returns a :class:`DecodeSpec`: what
state a layer keeps a position (the pools that
:class:`~distkeras_tpu.serving.cache.PagedKVCache` builds), and the model's
own embedding, prefill layer, single-token step layer and head as functions
the engine's programs call.  All functions are pure and are traced inside
the engine's jitted programs; they take the weights as their first argument
(``spec.params()``, passed to every program as runtime buffers, so that a
hot-swap reuses the compiled programs) and close over static sizes only.

This module also holds the one block the repo's own LMs share
(:func:`transformer_decode_spec`: pre-LayerNorm, learned positions, full
multi-head attention, GELU), which ``TransformerLM`` and ``StagedLM`` hand
their weights to.  It re-runs the model's own flax submodules
(``nn.LayerNorm`` / ``nn.DenseGeneral`` / ``nn.Dense`` and the
``_decode_attention`` masking math), so greedy requests emit tokens bitwise
identical to ``greedy_generate``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = ["DecodeSpec", "transformer_decode_spec"]


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """What a causal LM hands the serving engine.

    ``state``
        The per-layer state a position keeps, as ``(name, row_width)`` pools
        a layer: ``(("k", 768), ("v", 768))`` for GPT-2's block, ``(("latent",
        576),)`` for latent attention, ``(("latent_0", 576), ("latent_1",
        576))`` for a double layer of two latent attentions: two pools of the
        same kind, each written and read by its own sub-block within one
        ``prefill`` or ``step`` call (``num_layers`` then counts the double
        layers).  Every layer keeps the same kinds.
    ``weights``
        The pytree every program takes as its first argument (``params()``).
    ``num_layers``, ``max_len``, ``vocab_size``
        The static sizes the engine needs; ``geometry`` is what two specs
        must share for a hot-swap to reuse the compiled programs.
    ``embed(weights, tokens, positions) -> x``
        ``tokens`` and ``positions`` ``[batch, rows]`` int32; ``x [batch,
        rows, dim]``.
    ``prefill(weights, layer, x, positions, write, live) -> (x, aux)``
        One layer over a ``[1, width]`` chunk that starts at position 0:
        attends causally within the chunk and hands each pool's rows of the
        whole chunk to ``write(name, rows [width, ...])``, which stores them
        through the slot's page table.  ``live [1, width]`` marks the rows
        that are the prompt's (the rest is padding).  ``aux`` is what the
        block's own counters read of this layer (below), else None.
    ``step(weights, layer, x, pools, tables, pos, live) -> (pools, x, aux)``
        One layer, one token a slot: ``x [slots, 1, dim]``, ``pools`` a dict
        ``name -> [pages, page_size, row_width]`` of this layer, ``tables
        [slots, pages_per_slot]``, ``pos [slots]``.  Writes the step's rows
        at ``pos`` and attends over positions ``0 .. pos``.  ``live [slots,
        1]`` marks the slots that hold a request.
    ``head(weights, x, at=None) -> logits``
        Final norm and head over ``x [batch, rows, dim]``; with ``at`` (a
        traced row index into a ``[1, width, dim]`` chunk) that row's logits
        ``[vocab]`` alone.
    ``window(weights, layer, x, pools, tables, pos) -> (pools, x)``
        Optional: ``step`` over ``m`` consecutive tokens a slot (``x [slots,
        m, dim]``), the speculative verify.  A block without it cannot be
        the target of ``draft_model=``.
    ``shard(axis, size) -> DecodeSpec``
        Optional: the tensor-parallel twin, for programs that run inside
        ``shard_map`` over ``axis``: its functions reduce over the axis and
        it carries ``param_specs`` (a ``PartitionSpec`` tree like
        ``weights``) and ``pool_specs`` (``name -> PartitionSpec``).  A block
        without it cannot be served with ``mesh=``.
    ``instruments(registry) -> dict`` and ``observe(instruments, aux, rows, step)``
        Optional: the block's own always-on counters.  With them, the
        engine's programs hand the layers' ``aux`` (a pytree of small arrays,
        or None, a layer) back with the tokens, and ``observe`` gets the
        tuple of them one program behind, as numpy, with ``rows`` (the live
        rows the program ran) and ``step`` (True for a decode step).
    """

    state: Tuple[Tuple[str, int], ...]
    weights: Any
    num_layers: int
    max_len: int
    vocab_size: int
    geometry: Tuple
    embed: Callable
    prefill: Callable
    step: Callable
    head: Callable
    window: Optional[Callable] = None
    shard: Optional[Callable] = None
    param_specs: Any = None
    pool_specs: Any = None
    instruments: Optional[Callable] = None
    observe: Optional[Callable] = None

    def params(self):
        """The pytree passed (not closed over) to the jitted programs, so
        big leaves ride as runtime buffers rather than baked constants."""
        return self.weights


# ------------------------------------------------- the GPT-2-shaped block


def _block_apply(bp, x, attend, eps, psum=None):
    """One encoder block over param subtree ``bp``, reusing the model's own
    flax submodules so the math is bit-identical to training/`generate`.
    ``attend(q, k, v)`` supplies the paged-cache attention.  Head counts are
    read off the (possibly shard-local) kernel shapes, so the same function
    serves both the replicated and the tensor-parallel build; ``psum`` is
    the cross-shard reduction under ``shard_map`` (None when unsharded)."""
    ap = bp["_SelfAttention_0"]
    dim = bp["Dense_1"]["kernel"].shape[-1]
    mlp = bp["Dense_0"]["kernel"].shape[-1]
    heads, head_dim = ap["qkv"]["kernel"].shape[-2:]
    h = nn.LayerNorm(epsilon=eps).apply({"params": bp["LayerNorm_0"]}, x)
    qkv = nn.DenseGeneral((3, heads, head_dim)).apply({"params": ap["qkv"]}, h)
    q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
    out = attend(q, k, v)
    if psum is None:
        h = nn.DenseGeneral(dim, axis=(-2, -1)).apply({"params": ap["proj"]}, out)
    else:
        # tensor-parallel: each shard contracts its local heads bias-free,
        # the psum sums the partials, and the replicated bias is added once
        # (DenseGeneral per shard would add it axis-size times)
        h = jnp.einsum("...hd,hdo->...o", out, ap["proj"]["kernel"])
        h = psum(h) + ap["proj"]["bias"]
    x = x + h
    h = nn.LayerNorm(epsilon=eps).apply({"params": bp["LayerNorm_1"]}, x)
    h = nn.Dense(mlp).apply({"params": bp["Dense_0"]}, h)
    h = nn.gelu(h)
    h = nn.Dense(dim).apply({"params": bp["Dense_1"]}, h)
    return x + h


def transformer_decode_spec(*, tok, pos, blocks, final_ln, head, dim, heads,
                            head_dim, max_len, vocab_size, ln_eps,
                            _axis=None) -> DecodeSpec:
    """The :class:`DecodeSpec` of a stack of ``TransformerEncoderBlock``\\ s
    between token-plus-position embeddings and a LayerNorm + Dense head:
    two pools a layer (``k`` and ``v``, a token's heads side by side in a
    row), a dense causal attention of the chunk's width for the prefill,
    :func:`~distkeras_tpu.serving.cache.paged_decode_attention` for the step
    and a gathered window for the speculative verify.  ``blocks`` is the
    list of per-block param subtrees as flax names them."""
    from distkeras_tpu.serving.cache import append_rows, paged_decode_attention

    eps = float(ln_eps)
    psum = None if _axis is None else (lambda x: jax.lax.psum(x, _axis))
    weights = {"tok": jnp.asarray(tok), "pos": jnp.asarray(pos),
               "blocks": list(blocks), "final_ln": final_ln, "head": head}

    def embed(params, tokens, positions):
        return params["tok"][tokens] + params["pos"][
            jnp.clip(positions, 0, max_len - 1)]

    def prefill(params, li, x, positions, write, live):
        width = x.shape[1]

        def attend(q, k, v):
            # stash the whole padded chunk into this slot's pages; rows past
            # the prompt land on scratch/overwritten pages and are causally
            # masked below — never attended.
            write("k", k[0])
            write("v", v[0])
            # causal attention over the chunk itself (same masking math as
            # _SelfAttention._decode_attention)
            qt = jnp.moveaxis(q, 1, 2)
            kt = jnp.moveaxis(k, 1, 2)
            vt = jnp.moveaxis(v, 1, 2)
            scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
            s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
            q_pos = jnp.arange(width)[:, None]
            k_pos = jnp.arange(width)[None, :]
            s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
            out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vt)
            return jnp.moveaxis(out, 1, 2)

        return _block_apply(params["blocks"][li], x, attend, eps,
                            psum=psum), None

    def step(params, li, x, pools, tables, pos, live):
        pools = dict(pools)

        def attend(q, k, v):
            pools["k"], pools["v"], out = paged_decode_attention(
                pools["k"], pools["v"], tables, pos, q, k, v)
            return out

        x = _block_apply(params["blocks"][li], x, attend, eps, psum=psum)
        return pools, x, None

    def window(params, li, x, pools, tables, pos):
        pools = dict(pools)
        s, m = x.shape[:2]
        positions = pos[:, None] + jnp.arange(m)[None, :]  # [slots, m]

        def attend(q, k, v):
            pools["k"] = append_rows(pools["k"], tables, pos, k)
            pools["v"] = append_rows(pools["v"], tables, pos, v)
            ctx = tables.shape[1] * pools["k"].shape[1]
            kg = pools["k"][tables].reshape(s, ctx, *k.shape[-2:])
            vg = pools["v"][tables].reshape(s, ctx, *v.shape[-2:])
            scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
            sc = jnp.einsum("smhd,skhd->smhk", q, kg) * scale
            mask = jnp.arange(ctx)[None, None, :] <= positions[:, :, None]
            sc = jnp.where(mask[:, :, None, :], sc, -jnp.inf)
            return jnp.einsum("smhk,skhd->smhd", jax.nn.softmax(sc, axis=-1), vg)

        x = _block_apply(params["blocks"][li], x, attend, eps, psum=psum)
        return pools, x

    def head_fn(params, x, at=None):
        h = nn.LayerNorm(epsilon=eps).apply({"params": params["final_ln"]}, x)
        logits = nn.Dense(params["head"]["kernel"].shape[-1]).apply(
            {"params": params["head"]}, h)
        if at is None:
            return logits
        return jax.lax.dynamic_index_in_dim(logits[0], at, axis=0,
                                            keepdims=False)

    def shard(axis, size):
        """qkv sharded over heads, attention proj contracting over the
        sharded heads, everything else (embeddings, LN, MLP, head)
        replicated; a row of a pool holds the heads side by side, so
        sharding it shards the heads."""
        from jax.sharding import PartitionSpec as P

        if heads % size:
            raise ValueError(
                f"model heads {heads} not divisible by mesh size {size}")
        twin = transformer_decode_spec(
            tok=tok, pos=pos, blocks=blocks, final_ln=final_ln, head=head,
            dim=dim, heads=heads, head_dim=head_dim, max_len=max_len,
            vocab_size=vocab_size, ln_eps=ln_eps, _axis=axis)
        specs = jax.tree.map(lambda _: P(), twin.weights)
        for bs in specs["blocks"]:
            ap = bs["_SelfAttention_0"]
            ap["qkv"]["kernel"] = P(None, None, axis, None)
            ap["qkv"]["bias"] = P(None, axis, None)
            ap["proj"]["kernel"] = P(axis, None, None)
        pool = P(None, None, axis)
        return dataclasses.replace(
            twin, param_specs=specs, pool_specs={"k": pool, "v": pool})

    width = int(heads) * int(head_dim)
    return DecodeSpec(
        state=(("k", width), ("v", width)), weights=weights,
        num_layers=len(weights["blocks"]), max_len=int(max_len),
        vocab_size=int(vocab_size),
        geometry=(("dim", int(dim)), ("heads", int(heads)),
                  ("head_dim", int(head_dim)), ("max_len", int(max_len)),
                  ("vocab", int(vocab_size)), ("ln_eps", eps),
                  ("depth", len(weights["blocks"]))),
        embed=embed, prefill=prefill, step=step, head=head_fn, window=window,
        shard=shard)
