"""Continuous-batching serving engine: one jitted decode step, forever.

The generation story before this module was *call-shaped*:
``greedy_generate`` compiles one program per ``(model, steps)`` and runs a
whole batch in lockstep — every sequence starts together, finishes together,
and the program is torn through per call.  An online service sees none of
that structure: requests arrive whenever, want different lengths and
sampling, and must not pay a compile.  This engine is the standard
continuous-batching formulation (Orca/vLLM):

* a fixed ring of ``num_slots`` **batch slots**;
* a **block contract** (:class:`distkeras_tpu.models.decode.DecodeSpec`,
  what a model's ``decode_spec(params)`` hook returns): the model says what
  state a layer keeps a position (``(name, row_width)`` pools a layer) and
  supplies its own embedding, prefill layer, single-token step layer and
  head; the engine keeps slots, pages, programs, sampling and the loop, and
  names no submodule and holds no attention arithmetic of any block.
  ``TransformerLM`` and ``StagedLM`` (two pools a layer, keys and values),
  ``LatentMoELM`` (one latent pool a layer), ``ShortcutMoELM`` (two latent
  pools a double layer), ``WindowMoELM`` (paged keys and values in its
  global layers, a ring of the window's rows a slot in its sliding ones)
  and ``JambaLM`` (paged keys and values in its attention layers, a
  recurrent state a slot in its Mamba layers) are served by the same loop
  and the same programs' builders;
* ONE jitted single-token **decode step** over all slots — every
  per-request quantity (position, last token, RNG key,
  temperature/top-k/top-p, active flag, speculative opt-in) is *data*, so
  admitting or retiring a request never retraces (dklint DK102);
* a **paged cache** (:mod:`distkeras_tpu.serving.cache`): for each kind of
  state the block declares, pools shared by all slots (one ``[pages, page,
  row_width]`` array a layer, donated to every program and written in
  place), per-slot page tables, pages allocated at admission and freed at
  retirement.  A single-token step writes one row a slot and layer and
  reads the pages block by block, on a TPU each slot as far as it reaches
  (:func:`~distkeras_tpu.serving.cache.paged_decode_attention` for keys and
  values, one Pallas kernel there), every slot as far as the longest live
  one reaches elsewhere and for a latent row
  (:func:`~distkeras_tpu.serving.cache.paged_latent_attention`): its cost
  follows what the slots hold, not what they could.
  A kind that a window bounds (``DecodeSpec.sliding``) lives in a ring of
  ``window`` rows a slot instead, set up whole by the admission's prefill
  and read whole by the step
  (:func:`~distkeras_tpu.serving.cache.sliding_decode_attention`); a
  recurrent kind (``DecodeSpec.recurrent``) in one state a slot, replaced
  whole by the admission's prefill and advanced by the step, each store in
  its own type (``DecodeSpec.dtypes``);
* between decode steps the host loop **admits** queued requests into free
  slots (prefill) and **retires** finished ones (EOS / max-new-tokens), so
  a long request never convoys short ones;
* the loop **dispatches ahead**: what only the device knows (the token just
  sampled, the RNG keys) is chained from one program into the next as
  device arrays, and the host reads every program's tokens *one program
  behind*, while the next one runs — its uploads, dispatches, read-backs
  and bookkeeping pass under device time instead of between programs.
  The host knows a request's end by length as a count, so a slot is given
  back with its last step's dispatch; an EOS is seen one step late (the
  slot rides that step, its token is dropped).  Wherever the host must see
  the truth (before it sleeps, hot-swap, cancel, drain, crash, stop) it
  reads everything first.  One program behind, always: no depth to choose.
  (The speculative loop is accepted by count, which the host needs before
  the next window: it stays serial.);
* SLO metrics through the telemetry registry — TTFT and per-token-latency
  histograms, queue depth, token/request counters — visible on the
  flightdeck ``/metrics`` scrape.

Fast paths (each optional, all compile-count pinned):

* **Prefill width bucketing** — prompts prefill at the smallest
  power-of-two page-multiple width that fits them (``prefill_buckets``)
  instead of the slot's full page capacity, so a 12-token prompt stops
  paying max-context FLOPs.  One program per *used* bucket, compiled
  lazily; ``serving_prefill_padded_tokens`` counts the padding burned so
  the win is visible on ``/metrics``.
* **Speculative decoding** (``draft_model``) — a cheaper draft model
  (anything with a ``decode_spec``, e.g. a shallower ``TransformerLM``;
  the target's block must bring the multi-token ``window`` step, which
  ``LatentMoELM``'s does not yet: the engine refuses it at construction)
  proposes ``spec_tokens`` tokens per engine iteration via single-token
  draft steps; ONE multi-token target step verifies the window against the
  paged cache and emits the accepted prefix plus a correction token
  (Leviathan et al., arXiv:2211.17192 — see
  :func:`distkeras_tpu.serving.sampling.speculative_verify`).  There is no
  bonus token, so draft and target caches never develop holes.  Greedy
  emitted tokens are always target-argmax rows, hence bitwise identical to
  the non-speculative greedy stream regardless of draft quality; stochastic
  requests use exact acceptance-rejection resampling.  Requests opt out per
  call (``speculative=False``) and ride the same program as traced data.
* **Sharded decode** (``mesh``) — the target's prefill/decode/verify
  programs run under a tensor-parallel ``shard_map`` as the block's
  ``shard`` twin lays it out (GPT-2's block: heads sharded, MLP and
  embeddings replicated), so one engine serves from every local device.  A
  block without a twin (``LatentMoELM``) is refused at construction.

Numerics: the GPT-2-shaped block (``models/decode.py``) re-runs the model's
own flax submodules (``nn.LayerNorm`` / ``nn.DenseGeneral`` / ``nn.Dense`` /
the ``_decode_attention`` masking math) over its param subtrees, so greedy
requests emit tokens **bitwise
identical** to ``greedy_generate`` (tests/test_serving.py pins this under
staggered concurrent arrival).  Prefill pads the prompt to its bucket
width — positions past the prompt are causally masked and their cache rows
are overwritten by decode before ever becoming visible, so padding changes
nothing but FLOPs.

RNG: each request carries its own ``PRNGKey(seed)`` chain, split once per
engine iteration *of that request* — sampled output is a function of
(params, prompt, knobs, seed) alone, independent of whatever else shares
the batch.  Speculative opt-out slots consume the exact non-speculative
key chain, so a request's tokens don't change when its neighbours opt in.
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import chaos as _chaos
from distkeras_tpu.sanitizer import lockwatch
from distkeras_tpu.telemetry import accounting as _accounting
from distkeras_tpu.telemetry import runtime as _truntime
from distkeras_tpu.telemetry.trace import NOOP_SPAN, trace as _trace
from distkeras_tpu.serving.cache import (
    PagedKVCache,
    decode_block_pages,
    fit_rows,
    rollback_rows,
    set_slot_state,
    sliding_prefill_rows,
)
from distkeras_tpu.serving.frontend import (
    GenerateRequest,
    GenerateResult,
    RequestQueue,
)
from distkeras_tpu.serving.sampling import (
    modified_probs,
    sample_one,
    sample_tokens,
    sampling_level,
    speculative_verify_tokens,
)

__all__ = ["EngineCrashed", "ServingEngine", "serving_metrics"]


class EngineCrashed(RuntimeError):
    """The engine's host loop died (chaos ``kill_replica`` or an equivalent
    hard fault): every request aborted, the replica is dead.  Raised by
    ``submit``/``hot_swap`` so a router can tell "dead" from "saturated"."""


def serving_metrics(registry=None) -> dict:
    """Get-or-create the engine's SLO instruments on ``registry`` (default:
    the process-global one).  One canonical home for the names/help text so
    the engine, the golden test, and the CI smoke assert the same schema."""
    if registry is None:
        from distkeras_tpu.telemetry.metrics import metrics as registry
    return {
        "ttft": registry.histogram(
            "serving_ttft_seconds",
            help="time from request admission-queue entry to first token",
        ),
        "token_latency": registry.histogram(
            "serving_token_latency_seconds",
            help="the loop thread's time in one decode step's call: the "
                 "dispatch of this step, then the wait for and read-back of "
                 "what lies behind it (the step before, this iteration's "
                 "prefills); not this step's device time",
        ),
        "prefill_seconds": registry.histogram(
            "serving_prefill_seconds",
            help="the host's dispatch of one prefill alone (bucketed "
                 "width): inputs built and uploaded, the program enqueued; "
                 "its first token is read one program behind",
        ),
        "queue_depth": registry.gauge(
            "serving_queue_depth", help="requests waiting for a batch slot"
        ),
        "active_slots": registry.gauge(
            "serving_active_slots", help="batch slots generating right now"
        ),
        "pages_in_use": registry.gauge(
            "serving_kv_pages_in_use", help="allocated KV cache pages"
        ),
        "tokens": registry.counter(
            "serving_tokens_total", help="tokens generated across all requests"
        ),
        "requests": registry.counter(
            "serving_requests_total", help="requests completed (any finish reason)"
        ),
        "rejected": registry.counter(
            "serving_requests_rejected_total",
            help="requests shed by queue backpressure",
        ),
        "prefill_padded": registry.counter(
            "serving_prefill_padded_tokens",
            help="padding tokens burned by bucketed prefill (width - prompt)",
        ),
        "decode_steps": registry.counter(
            "serving_decode_steps_total",
            help="target decode/verify iterations (speculative emits >1 "
                 "token per step, so steps/tokens < 1)",
        ),
        "decode_chained": registry.counter(
            "serving_decode_steps_chained_total",
            help="decode steps dispatched while the step before them was "
                 "still unread by the host: their token and key inputs were "
                 "that step's device outputs",
        ),
        "decode_sampled": registry.counter(
            "serving_decode_steps_sampled_total",
            help="decode steps in which some active slot sampled "
                 "(temperature > 0), so the step drew from a distribution; "
                 "the other steps took the argmax and nothing else",
        ),
        "decode_sorted": registry.counter(
            "serving_decode_steps_sorted_total",
            help="decode steps that sorted every slot's vocabulary: some "
                 "active sampling slot had a top-k or a top-p, or the step "
                 "was a speculative verify iteration",
        ),
        "spec_proposed": registry.counter(
            "serving_spec_proposed_total",
            help="draft tokens proposed by speculative decoding",
        ),
        "spec_accepted": registry.counter(
            "serving_spec_accepted_total",
            help="draft tokens accepted by target verification",
        ),
        "hot_swaps": registry.counter(
            "serving_hot_swaps_total",
            help="in-place param hot-swaps applied by this engine",
        ),
        "kv_read": registry.counter(
            "serving_decode_kv_positions_read_total",
            help="cache positions the single-token steps' attention was told "
                 "to cover in the paged layers: per active slot, pos + 1 "
                 "rounded up to the block (a sliding-window layer's ring is "
                 "the block's own to count)",
        ),
        "kv_capacity": registry.counter(
            "serving_decode_kv_positions_capacity_total",
            help="cache positions those steps could cover: slots x max context",
        ),
        "state_bytes": registry.gauge(
            "serving_state_per_position_bytes",
            help="bytes of paged state one position keeps over all layers "
                 "and kinds of state the served block declares",
        ),
        "slot_state_bytes": registry.gauge(
            "serving_state_per_slot_bytes",
            help="bytes of fixed state one slot keeps whatever its length: "
                 "the rows of the kinds that a window bounds (sliding-window "
                 "layers) and of the recurrent states (state-space layers), "
                 "over the layers that keep them, each in its store's type",
        ),
        # the loop thread's account of its own time: one observation a span
        # of the same name in the flight-recorder ring (serving.loop*)
        "loop_iteration": registry.histogram(
            "serving_loop_iteration_seconds",
            help="one iteration of the host loop that admitted or stepped: "
                 "from its top to the return of its decode step's call",
        ),
        "loop_dispatch": registry.histogram(
            "serving_loop_dispatch_seconds",
            help="a decode step's cost to the host before it turns to "
                 "reading: counters, inputs (uploaded when an admit or a "
                 "retire changed them), the program's dispatch, bookkeeping",
        ),
        "loop_wait": registry.histogram(
            "serving_loop_wait_seconds",
            help="one blocking read of a dispatched program's tokens: the "
                 "loop thread with nothing to do but wait for the device",
        ),
        "loop_emit": registry.histogram(
            "serving_loop_emit_seconds",
            help="handing one read program's tokens to their requests: the "
                 "block's counters, the per-slot bookkeeping, the callers "
                 "woken",
        ),
        "loop_idle": registry.histogram(
            "serving_loop_idle_seconds",
            help="an iteration that found nothing to do: its read of what "
                 "was in flight and its sleep",
        ),
        "queue_wait": registry.histogram(
            "serving_queue_wait_seconds",
            help="an admitted request's time in the queue: its enqueue to "
                 "its prefill",
        ),
        "dispatches": registry.counter(
            "serving_dispatches_total",
            help="programs dispatched to the device: prefills, decode steps, "
                 "draft and verify steps",
        ),
        "dispatches_starved": registry.counter(
            "serving_dispatches_starved_total",
            help="dispatches that found the device empty while the host "
                 "worked: the newest unread program was already finished, or "
                 "none was unread and the loop had not slept for want of "
                 "requests since its last dispatch (the serial speculative "
                 "loop keeps none unread, so all of its count)",
        ),
        "gc_pause": registry.counter(
            "serving_gc_pause_seconds_total",
            help="seconds the interpreter's garbage collections took, on "
                 "any thread, while the loop was inside an iteration",
        ),
    }


# ----------------------------------------------------------- the served model


def _resolve_spec(model, params):
    """The model's :class:`~distkeras_tpu.models.decode.DecodeSpec`.  Accepts
    a ``TrainedModel``, a ``FlaxModel`` adapter + params, or a raw
    module/adapter with a ``decode_spec`` hook + params."""
    from distkeras_tpu.models.adapter import FlaxModel, TrainedModel

    if isinstance(model, TrainedModel):
        return _resolve_spec(model.adapter, model.params)
    if isinstance(model, FlaxModel):
        model = model.module
    hook = getattr(model, "decode_spec", None)
    if hook is None:
        raise TypeError(
            f"{type(model).__name__} has no decode_spec hook; serving "
            "supports TransformerLM, StagedLM, LatentMoELM, ShortcutMoELM, "
            "WindowMoELM and JambaLM"
        )
    if params is None:
        raise ValueError(
            "params required when passing a bare module/adapter "
            "(a TrainedModel carries its own)"
        )
    return hook(params)


def _donated(spec) -> Tuple[int, ...]:
    """A program's donated arguments: one tuple of pools a kind of state,
    right behind the weights."""
    return tuple(range(1, 1 + len(spec.state)))


def _take_pools(spec, args):
    """A program's arguments behind the weights, split into its pools
    (``name -> list of per-layer arrays``) and its own inputs."""
    names = [name for name, _ in spec.state]
    return ({n: list(p) for n, p in zip(names, args)}, args[len(names):])


def _give_pools(spec, pools):
    """The pools as a program hands them back, ahead of its other outputs."""
    return tuple(tuple(pools[name]) for name, _ in spec.state)


def _resolve_buckets(prefill_buckets, page_size: int, max_context: int):
    """The prefill width ladder: ascending page-multiple widths ending at
    ``max_context``.  Default: ``page_size * 2**i`` capped at capacity."""
    if prefill_buckets is None:
        widths, w = [], page_size
        while w < max_context:
            widths.append(w)
            w *= 2
        widths.append(max_context)
        return tuple(widths)
    widths = sorted({int(w) for w in prefill_buckets})
    if not widths:
        raise ValueError("prefill_buckets must be non-empty")
    for w in widths:
        if w < 1 or w > max_context or w % page_size:
            raise ValueError(
                f"prefill bucket {w} must be a positive multiple of "
                f"page_size {page_size} and <= max context {max_context}"
            )
    if widths[-1] != max_context:
        widths.append(max_context)  # every admissible prompt needs a bucket
    return tuple(widths)


# -------------------------------------------------------------- bookkeeping


class _Pending:
    """Handle returned by :meth:`ServingEngine.submit` — resolves to a
    :class:`GenerateResult` when the request retires."""

    __slots__ = ("request", "max_new", "enqueue_t", "_event", "_result")

    def __init__(self, request: GenerateRequest, max_new: int, enqueue_t: float):
        self.request = request
        self.max_new = max_new
        self.enqueue_t = enqueue_t
        self._event = threading.Event()
        self._result: Optional[GenerateResult] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> Optional[GenerateResult]:
        """Block for the result; ``None`` on timeout."""
        if not self._event.wait(timeout):
            return None
        return self._result

    def _resolve(self, result: GenerateResult) -> None:
        self._result = result
        self._event.set()


class _SlotState:
    """Host-side record for one request from its prefill's dispatch to its
    answer.  It holds a batch slot until its last decode step has been
    dispatched (``steps_left`` reaches 0) or it is retired; its tokens
    arrive one program behind, so it can outlive the slot by one read."""

    __slots__ = ("pending", "tokens", "plen", "ttft_s", "pages", "admit_t",
                 "steps_left", "done")

    def __init__(self, pending: _Pending, plen: int):
        self.pending = pending
        self.tokens: List[int] = []
        self.plen = plen
        self.ttft_s = 0.0
        self.pages = 0        # pages held — the page-seconds numerator
        self.admit_t = 0.0    # prefill-dispatched wall time — its clock start
        # decode steps still to dispatch: the prefill makes the first token
        self.steps_left = pending.max_new - 1
        self.done = False     # resolved: a token that arrives later is dropped


class _InFlight:
    """One dispatched program whose sampled tokens the host has not read:
    ``tok`` is the device array (``[slots]`` of a decode step, a scalar of a
    prefill), ``rows`` the ``(slot, state)`` pairs it sampled for, and
    ``prefill`` a prefill's ``(queue wait, loop time)`` for the ledger,
    ``aux`` what a block with counters of its own handed back beside,
    ``seq`` its place in the engine's order of dispatch."""

    __slots__ = ("tok", "rows", "prefill", "aux", "seq")

    def __init__(self, tok, rows, seq, prefill=None, aux=None):
        self.tok = tok
        self.rows = rows
        self.seq = seq
        self.prefill = prefill
        self.aux = aux  # the block's own counts, for its ``observe``


# -------------------------------------------------------------------- engine


class ServingEngine:
    """Online inference engine with continuous batching over a paged KV
    cache.  See the module docstring for the design; quick start::

        engine = ServingEngine(trained_model, num_slots=4, page_size=16)
        out = engine.generate([1, 2, 3], max_new_tokens=8)   # blocking
        pending = engine.submit(GenerateRequest(prompt=[1, 2, 3]))  # async
        result = pending.result(timeout=30)
        engine.stop()

    The host loop runs on a daemon thread started lazily by the first
    ``submit``/``generate`` (or explicitly via :meth:`start`).  ``model``
    is a ``TrainedModel``, or anything with a ``decode_spec(params)`` hook
    (``TransformerLM``/``StagedLM``, raw or behind ``FlaxModel``;
    ``LatentMoELM``) plus ``params``.

    The loop never blocks on the program it has just dispatched.  The
    decode step's token, position and key inputs are the device outputs
    of the program before it (a prefill seats its first token and key
    into the slot's place on the device); tables, temperature, top-k,
    top-p and the active flags are uploaded when an admit or a retire
    changed them.  Tokens reach the host, and ``ttft_s`` is stamped, one
    program behind.  ``serving_decode_steps_chained_total`` over
    ``serving_decode_steps_total`` says how often a step was dispatched
    with the step before it still unread.  The step's sampling takes the
    cheapest path that gives the same tokens, chosen inside the program from
    those knobs (``sampling.sampling_level``): an ``argmax`` alone while no
    active slot samples, the sort over the vocabulary only where some
    sampling slot truncates; ``serving_decode_steps_sampled_total`` and
    ``serving_decode_steps_sorted_total`` count the steps that took more
    than the ``argmax``.  ``cancel`` / ``drain`` /
    ``hot_swap`` / ``stop`` and a crash read everything in flight first:
    the tokens a caller gets back are those the device made.  With a
    ``draft_model`` the loop is serial (the accepted count decides the
    next window).

    What a step reads: the decode step (and the draft's) never gathers a
    slot's whole window.  In each layer it writes the step's rows (K and V,
    or the one latent row) through the page table in place and attends over
    blocks of some 128 positions with an online softmax.  On a TPU the keys
    and values of pools the Pallas kernel takes are read slot by slot, each
    up to the block that holds its own position
    (``serving.cache.paged_decode_attention``); elsewhere, and for a latent
    row, every slot is read up to the block of the longest live slot's
    position and masked inside it.
    ``serving_decode_kv_positions_read_total`` over ``..._capacity_total``
    counts each active slot's own blocks against the slots' capacity.  The verify step (``spec_tokens`` rows a slot) and
    the prefill keep a dense attention of their own width.

    Fast-path knobs: ``prefill_buckets`` (width ladder; default
    power-of-two), ``draft_model``/``draft_params``/``spec_tokens``
    (speculative decoding), ``mesh`` (a 1-D tensor-parallel
    ``jax.sharding.Mesh``; ``heads`` must divide by its size).  The last two
    need the block's ``window`` and ``shard``; the expert blocks and
    ``JambaLM`` bring neither yet, and the constructor says so.

    The loop thread accounts for all of its own time, whether or not
    telemetry is on: every pass is a ``serving.loop`` span in the
    flight-recorder ring (``serving.loop.idle`` for one that found nothing
    to do) over ``serving.loop.admit`` / ``.prefill`` / ``.dispatch`` /
    ``.wait`` / ``.emit``, the timed ones also one observation of
    ``serving_loop_{iteration,dispatch,wait,emit,idle}_seconds`` from the
    same two clock reads; ``serving_loop_wait_seconds`` is the host's slack
    and iteration less wait its own work.  Every program dispatched gets a
    ``seq`` (dispatch order, which on one stream is execution order) and
    counts in ``serving_dispatches_total``, in
    ``serving_dispatches_starved_total`` too if it found the device empty
    while the host worked; ``serving_queue_wait_seconds`` is an admitted
    request's time in the queue and ``serving_gc_pause_seconds_total`` the
    interpreter's collections inside the loop's iterations.  The request's
    own spans (``serving.admit``, ``.queue_wait``, ``.prefill``,
    ``.decode_step``, ids and tenants on them) stay behind the switch.

    A block may bring counters of its own (``DecodeSpec.instruments`` /
    ``observe``: ``LatentMoELM``'s ``serving_moe_*``): its programs hand a
    few small arrays back beside the tokens, read one program behind with
    them.  ``serving_state_per_position_bytes`` is the declared paged state's
    size for any block, ``serving_state_per_slot_bytes`` the fixed state a
    slot keeps whatever its length (the rings of sliding-window layers and
    the recurrent states of state-space layers); ``stats()`` has both, and
    the per-slot bytes of the rings and the recurrent states apart.
    """

    def __init__(self, model, params=None, *, num_slots: int = 4,
                 page_size: int = 16, pages_per_slot: Optional[int] = None,
                 num_pages: Optional[int] = None, queue_size: int = 64,
                 registry=None, dtype=jnp.float32,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 draft_model=None, draft_params=None, spec_tokens: int = 4,
                 mesh=None):
        spec = _resolve_spec(model, params)

        # ------------------------------------------------ tensor parallelism
        self._mesh = mesh
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    "serving mesh must be 1-D (one tensor-parallel axis); "
                    f"got axes {mesh.axis_names}"
                )
            self._tp_axis = mesh.axis_names[0]
            spec = self._sharded(spec, type(model).__name__)
        if draft_model is not None and spec.window is None:
            raise ValueError(
                f"{type(model).__name__}'s block has no speculative-verify "
                "twin (DecodeSpec.window, a step over several tokens a "
                "slot), so it cannot be the target of speculative decoding: "
                "build the engine without draft_model="
            )
        self._spec = spec
        if pages_per_slot is None:
            pages_per_slot = -(-spec.max_len // page_size)
        self.num_slots = int(num_slots)
        self._cache = PagedKVCache(
            num_layers=spec.num_layers, num_slots=num_slots,
            page_size=page_size, pages_per_slot=pages_per_slot,
            state=spec.state, num_pages=num_pages, dtype=dtype,
            layer_kinds=spec.layer_kinds, sliding=spec.sliding,
            recurrent=spec.recurrent, dtypes=spec.dtypes,
        )
        if mesh is not None:
            from jax.sharding import NamedSharding

            for name, pools in self._cache.pools.items():
                self._cache.pools[name] = jax.device_put(
                    pools, NamedSharding(mesh, spec.pool_specs[name]))
        self._width = self._cache.max_context()
        # positions that a single-token step reads at a time (host-side twin
        # of paged_decode_attention's block, for the kv_read counter)
        self._kv_block = self._cache.page_size * decode_block_pages(
            self._cache.page_size, self._cache.pages_per_slot)
        # the logits' width, for the host's twin of the program's choice of
        # a sampling path (geometry: a hot swap keeps it)
        self._vocab = spec.vocab_size
        self._buckets = _resolve_buckets(
            prefill_buckets, self._cache.page_size, self._width)
        self._queue = RequestQueue(queue_size)
        self._metrics = serving_metrics(registry)
        self._metrics["state_bytes"].set(self._cache.bytes_per_position())
        self._metrics["slot_state_bytes"].set(self._cache.bytes_per_slot())
        # the block's own counters (a DecodeSpec with ``instruments``): its
        # programs hand their small arrays back with the tokens
        self._observe = None
        if spec.observe is not None:
            if registry is None:
                from distkeras_tpu.telemetry.metrics import metrics as registry
            self._observe = functools.partial(
                spec.observe, spec.instruments(registry))
        # per-tenant ledger (None when DISTKERAS_ACCOUNTING is off): every
        # billing site meters from already-host-visible bookkeeping, so the
        # flag-off path keeps a single `is None` check and the traced
        # programs are byte-identical either way
        self._ledger = _accounting.maybe_ledger(registry)

        # --------------------------------------------------- draft / verify
        self._draft_spec = None
        self._draft_cache = None
        self._spec_tokens = int(spec_tokens)
        if draft_model is not None:
            if self._spec_tokens < 1:
                raise ValueError("spec_tokens must be >= 1")
            dspec = _resolve_spec(draft_model, draft_params)
            if dspec.sliding or dspec.recurrent:
                raise ValueError(
                    "the draft's block keeps sliding-window or recurrent "
                    "state, which its cache-only prefill cannot set up: pick "
                    "a draft without (draft_model=)")
            if dspec.vocab_size != spec.vocab_size:
                raise ValueError(
                    f"draft vocab {dspec.vocab_size} != target vocab "
                    f"{spec.vocab_size}"
                )
            serviceable = min(self._width, spec.max_len)
            if dspec.max_len < serviceable:
                raise ValueError(
                    f"draft max_len {dspec.max_len} < serviceable context "
                    f"{serviceable}; pick a draft trained at the same length"
                )
            self._draft_spec = dspec
            # same page geometry so the target's page tables address the
            # draft pools directly; bookkeeping (free list) is never used —
            # the draft is replicated even under a mesh (it's cheap by
            # construction, and sharding it would serialize two shard_maps)
            self._draft_cache = PagedKVCache(
                num_layers=dspec.num_layers, num_slots=num_slots,
                page_size=page_size, pages_per_slot=pages_per_slot,
                state=dspec.state, num_pages=self._cache.num_pages,
                dtype=dtype,
            )

        s = self.num_slots
        self._slots: List[Optional[_SlotState]] = [None] * s
        # host mirrors, advanced by count (never read back from the device)
        self._pos = np.zeros(s, np.int32)        # position of the fed token
        self._temp = np.zeros(s, np.float32)
        self._topk = np.zeros(s, np.int32)
        self._topp = np.ones(s, np.float32)
        self._active = np.zeros(s, bool)
        self._spec_on = np.zeros(s, bool)
        # what only the device knows.  The speculative loop is serial and
        # keeps it in these host arrays; the plain loop chains it on the
        # device (_dev) and never reads the keys back at all.
        self._last = np.zeros(s, np.int32)       # token being fed this step
        self._keys = np.zeros((s, 2), np.uint32)
        self._draft_keys = np.zeros((s, 2), np.uint32)
        # the decode step's per-slot inputs as device arrays: last, keys and
        # pos are the previous program's outputs; tables, pos, temp, top_k,
        # top_p and active are uploaded again when an admit or a retire
        # changed them (_dirty), not every step
        self._dev: Dict[str, Any] = {
            "last": jnp.zeros(s, jnp.int32),
            "keys": jnp.zeros((s, 2), jnp.uint32),
        }
        self._dirty = True
        # sampling_level of the knobs as last uploaded: the path the decode
        # step takes, for the counters
        self._level = 0
        # programs whose tokens are still on the device, oldest first
        self._inflight: collections.deque = collections.deque()
        # the loop thread's account of itself (the serving.loop* spans and
        # the serving_loop_* instruments): the programs dispatched so far
        # (the next one's ``seq``), the loop's passes, whether it has slept
        # for want of requests since its last dispatch, this pass's starved
        # dispatches, and the requests resolved so far
        self._seq = 0
        self._iter = 0
        self._slept = True
        self._starved = 0
        self._resolved = 0

        self._cv = lockwatch.maybe_wrap(threading.Condition(), "serving.engine")
        self._running = False
        self._thread: Optional[threading.Thread] = None
        # tier hooks: drain/hot-swap/cancel state, all owned by the loop
        # thread except the flags themselves (set under _cv by callers)
        self._crashed = False
        self._draining = False
        self._drain_ack = False
        self._swap: Optional[Tuple[Any, threading.Event]] = None
        self._cancelled: List[_Pending] = []

        # Programs compile once per (engine, mesh) config — never per
        # request (the retrace pin in tests/test_serving.py counts on it):
        # one decode OR (one draft step + one verify), plus one prefill per
        # *used* bucket width, built lazily in _prefill_for.  Every program
        # takes the weights, then one tuple of per-layer pools a kind of
        # state (donated, written in place), then its own inputs.
        self._prefill_fns: Dict[Tuple[str, int], Any] = {}
        if self._draft_spec is None:
            self._decode = jax.jit(
                self._maybe_shard(self._build_decode(), n_rest=8, n_out=3),
                donate_argnums=_donated(spec))
        else:
            self._draft_step = jax.jit(
                self._build_draft_step(), donate_argnums=_donated(dspec))
            self._verify = jax.jit(
                self._maybe_shard(self._build_verify(), n_rest=11, n_out=4),
                donate_argnums=_donated(spec))

    # ------------------------------------------------------- traced programs

    def _sharded(self, spec, model):
        """The spec's tensor-parallel twin for this engine's mesh."""
        if spec.shard is None:
            raise ValueError(
                f"{model}'s block has no tensor-parallel twin "
                "(DecodeSpec.shard): build the engine without mesh="
            )
        return spec.shard(self._tp_axis, int(self._mesh.devices.size))

    def _maybe_shard(self, fn, n_rest: int, n_out: int):
        """Wrap a ``(params, *pools, *rest) -> (*pools, *outs)`` step in a
        tensor-parallel shard_map when the engine has a mesh.  Weights and
        pools are sharded as the block's twin says; every other input/output
        is replicated."""
        if self._mesh is None:
            return fn
        from jax.sharding import PartitionSpec as P

        from distkeras_tpu.utils import compat

        with self._cv:
            spec = self._spec
        pools = tuple((spec.pool_specs[name],) * spec.num_layers
                      for name, _ in spec.state)
        in_specs = (spec.param_specs,) + pools + (P(),) * n_rest
        out_specs = pools + (P(),) * n_out
        # check_vma=True: JAX proves what the P() out_specs claim — the
        # sampled outputs are replicated because the inputs are and every
        # cross-head contraction is psummed
        return compat.shard_map(
            fn, self._mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=True)

    @staticmethod
    def _pools_of(cache):
        """The cache's pools in the order of its state: a program's pool
        arguments."""
        return tuple(cache.pools[name] for name, _ in cache.state)

    @staticmethod
    def _keep(cache, out):
        """Take a program's pools back into ``cache`` (it donated them);
        returns the program's other outputs."""
        for (name, _), pools in zip(cache.state, out):
            cache.pools[name] = pools
        return out[len(cache.state):]

    def _prefill_for(self, width: int, role: str = "target"):
        """The jitted prefill program for one bucket width, compiled on
        first use.  ``role`` is "target" (samples the first token) or
        "draft" (cache writes only)."""
        key = (role, width)
        fn = self._prefill_fns.get(key)
        if fn is None:
            with self._cv:
                spec = self._spec
            if role == "target":
                fn = jax.jit(
                    self._maybe_shard(
                        self._build_prefill(width, spec, sample=True),
                        n_rest=10, n_out=3),
                    donate_argnums=_donated(spec))
            else:
                dspec = self._draft_spec
                fn = jax.jit(
                    self._build_prefill(width, dspec, sample=False),
                    donate_argnums=_donated(dspec))
            self._prefill_fns[key] = fn
        return fn

    def _build_prefill(self, width: int, spec, *, sample: bool):
        ps = self._cache.page_size
        npages = width // ps
        counted = sample and spec.observe is not None
        windows = dict(spec.sliding)
        recurrent = dict(spec.recurrent)

        def trunk(params, pools, tokens, table, live, length=None, slot=None):
            # tokens [1, width] right-padded; table [npages].
            positions = jnp.arange(width)[None]
            x = spec.embed(params, tokens, positions)
            aux = []
            for li in range(spec.num_layers):
                def write(name, rows, li=li):
                    pool = pools[name][li]
                    if name in windows:
                        # the prompt's last rows into the slot's ring
                        pools[name][li] = sliding_prefill_rows(
                            pool, slot, rows, length)
                        return
                    if name in recurrent:
                        # the state after the prompt replaces the slot's
                        pools[name][li] = set_slot_state(pool, slot, rows)
                        return
                    # the whole padded chunk into this slot's pages; rows
                    # past the prompt land on scratch/overwritten pages
                    pools[name][li] = pool.at[table].set(
                        fit_rows(rows.reshape(npages, ps, -1), pool))

                x, extra = spec.prefill(params, li, x, positions, write, live)
                aux.append(extra)
            return x, tuple(aux)

        if not sample:
            def prefill_cache_only(params, *args):
                # draft prefill: only the state's writes matter — XLA
                # dead-code eliminates the attention outputs, leaving the
                # cheap projections per layer
                pools, (tokens, table) = _take_pools(spec, args)
                trunk(params, pools, tokens, table,
                      jnp.ones((1, width), bool))
                return _give_pools(spec, pools)

            return prefill_cache_only

        def prefill(params, *args):
            pools, (tokens, table, length, key, temp, top_k, top_p, last,
                    keys, slot) = _take_pools(spec, args)
            x, aux = trunk(params, pools, tokens, table,
                           jnp.arange(width)[None] < length, length, slot)
            row = spec.head(params, x, at=length - 1)
            key, sub = jax.random.split(key)
            tok = sample_one(row, sub, temp, top_k, top_p)
            # seat the first token and the request's key into the slot's
            # place in the decode step's inputs, here on the device: the
            # next step can be dispatched before the host has seen either
            outs = _give_pools(spec, pools) + (
                tok, last.at[slot].set(tok), keys.at[slot].set(key))
            return outs + ((aux,) if counted else ())

        return prefill

    def _build_step(self, spec, *, name: str, qprobs: bool, counted: bool):
        """One single-token step over all slots, for the target
        (``decode``) or the draft (``draft_step``, which also returns the
        *modified* distribution it sampled from, the q of the acceptance
        test).  Every layer is the block's own ``step``: the step's rows are
        written in place and read with the pages that each slot holds.  After
        the pools its outputs are the tokens, ``pos`` advanced for the
        active slots and the keys: the next step's ``last``, ``pos`` and
        ``keys``."""

        def step(params, *args):
            pools, (tables, pos, last, keys, temp, top_k, top_p,
                    active) = _take_pools(spec, args)
            # One token for every slot.  Inactive slots compute garbage into
            # the scratch page (their tables point at physical page 0) and
            # sample token 0 — all masked out host-side.
            x = spec.embed(params, last[:, None], pos[:, None])
            aux = []
            for li in range(spec.num_layers):
                names = spec.kinds(li)
                layer, x, extra = spec.step(
                    params, li, x, {n: pools[n][li] for n in names}, tables,
                    pos, active[:, None])
                aux.append(extra)
                for n in names:
                    pools[n][li] = layer[n]
            logits = spec.head(params, x)[:, 0]
            split = jax.vmap(jax.random.split)(keys)
            new_keys, subs = split[:, 0], split[:, 1]
            tok = sample_tokens(logits, subs, temp, top_k, top_p, active)
            tok = jnp.where(active, tok, 0)
            outs = _give_pools(spec, pools) + (tok,)
            if qprobs:
                outs += (jax.vmap(modified_probs)(logits, temp, top_k, top_p),)
            # tok, the next positions and the keys are the next step's
            # last, pos and keys: the host chains them without reading them
            outs += (pos + active.astype(pos.dtype), new_keys)
            return outs + ((tuple(aux),) if counted else ())

        step.__name__ = name  # the program's name in a device trace
        return step

    def _build_decode(self):
        return self._build_step(self._spec, name="decode", qprobs=False,
                                counted=self._spec.observe is not None)

    def _build_draft_step(self):
        """The draft's single-token step.  Always replicated."""
        return self._build_step(self._draft_spec, name="draft_step",
                                qprobs=True, counted=False)

    def _build_verify(self):
        """The multi-token target step: feed the window ``[last, d_1 ..
        d_{m-1}]``, write its state through the page tables (the block's
        ``window``), compute all m next-token logits in one pass, judge the
        drafts per slot (:func:`speculative_verify_tokens`), and roll the
        rejected suffix rows back out of the pools."""
        spec = self._spec
        m = self._spec_tokens
        names = [n for n, _ in spec.state]

        def verify(params, *args):
            pools, (tables, pos, last, drafts, qprobs, keys, temp, top_k,
                    top_p, active, spec_on) = _take_pools(spec, args)
            # drafts: tuple of m [slots] proposals (d_1..d_m); qprobs: tuple
            # of m [slots, vocab] draft distributions.  Stacked here, inside
            # the program, so the host loop ships the draft step's outputs
            # without an extra dispatch.
            d = jnp.stack(drafts, axis=1)        # [slots, m]
            q_d = jnp.stack(qprobs, axis=1)      # [slots, m, vocab]
            fed = jnp.concatenate([last[:, None], d[:, :-1]], axis=1)
            positions = pos[:, None] + jnp.arange(m)[None, :]  # [slots, m]
            x = spec.embed(params, fed, positions)
            for li in range(spec.num_layers):
                layer, x = spec.window(
                    params, li, x, {n: pools[n][li] for n in names}, tables,
                    pos)
                for n in names:
                    pools[n][li] = layer[n]
            logits = spec.head(params, x)
            out, count, accepted, new_keys = speculative_verify_tokens(
                logits, d, q_d, keys, temp, top_k, top_p, spec_on & active)
            out = jnp.where(active[:, None], out, 0)
            # erase the rejected suffix so the pools only ever hold
            # accepted-token rows between iterations
            for n in names:
                pools[n] = [rollback_rows(pool, tables, pos, count, m)
                            for pool in pools[n]]
            return _give_pools(spec, pools) + (
                out, count, accepted, new_keys)

        return verify

    # ----------------------------------------------------------- public API

    def start(self) -> None:
        """Start the host loop thread (idempotent; ``submit`` calls this)."""
        _trace.watch_gc()  # the interpreter's pauses stall the loop thread
        with self._cv:
            if self._running:
                return
            self._running = True
            self._thread = threading.Thread(
                target=self._loop, name="serving-engine", daemon=True
            )
            self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        """Stop the loop; queued and in-flight requests resolve with
        ``finish_reason="aborted"`` (partial tokens included)."""
        with self._cv:
            if not self._running:
                thread = None
            else:
                self._running = False
                thread = self._thread
                self._thread = None
            self._cv.notify_all()
        if thread is not None:
            thread.join(timeout=timeout)
        if thread is None or not thread.is_alive():
            self._flush()  # the loop's own on its way out; here if it died
        for slot in range(self.num_slots):
            if self._slots[slot] is not None:
                self._retire(slot, "aborted")
        while True:
            pending = self._queue.pop()
            if pending is None:
                break
            self._finish(pending, [], "aborted", 0.0)
        self._metrics["queue_depth"].set(0)

    def submit(self, request: GenerateRequest) -> _Pending:
        """Validate + enqueue; returns a :class:`_Pending` handle.  Raises
        :class:`~distkeras_tpu.serving.frontend.QueueFull` under
        backpressure and ``ValueError`` for an unservable request.  The
        admission is a ``serving.admit`` span — on the caller's thread, so
        it nests under whatever hop span (``tier.attempt``,
        ``serving.http_request``) drove the submit."""
        span = NOOP_SPAN
        if _truntime.enabled():
            span = _trace.span(
                "serving.admit", request_id=request.request_id,
                trace_id=request.trace_id)
        with span:
            return self._submit(request)

    def _submit(self, request: GenerateRequest) -> _Pending:
        with self._cv:
            # snapshot the published spec: hot-swap replaces it under _cv,
            # so validating against a local ref sees one coherent geometry
            crashed, spec = self._crashed, self._spec
        if crashed:
            raise EngineCrashed("serving engine crashed; replica is dead")
        request.validate()
        plen = len(request.prompt)
        if plen > self._width or plen >= spec.max_len:
            raise ValueError(
                f"prompt length {plen} exceeds serviceable context "
                f"(width {self._width}, model max_len {spec.max_len})"
            )
        if int(np.max(request.prompt)) >= spec.vocab_size:
            raise ValueError("prompt token id out of vocabulary")
        if request.speculative and self._draft_spec is None:
            raise ValueError(
                "request asks for speculative decoding but the engine was "
                "built without a draft_model"
            )
        max_new = min(request.max_new_tokens, spec.max_len - plen,
                      self._width - plen)
        pending = _Pending(request, max_new, time.perf_counter())
        try:
            self._queue.put(pending)
        except Exception:
            self._metrics["rejected"].inc()
            raise
        self._metrics["queue_depth"].set(len(self._queue))
        self.start()
        with self._cv:
            self._cv.notify_all()
        return pending

    def generate(self, prompt, max_new_tokens: int = 16,
                 timeout: Optional[float] = 60.0,
                 **knobs) -> GenerateResult:
        """Blocking convenience: submit one request, wait for its result.
        ``knobs`` forwards temperature/top_k/top_p/seed/eos_id/speculative."""
        req = GenerateRequest(prompt=[int(t) for t in prompt],
                              max_new_tokens=max_new_tokens, **knobs)
        result = self.submit(req).result(timeout=timeout)
        if result is None:
            raise TimeoutError(f"generation did not finish in {timeout}s")
        return result

    def stats(self) -> Dict[str, float]:
        """Host-side snapshot for bench/debug (not the metrics surface)."""
        return {
            "queue_depth": float(len(self._queue)),
            "active_slots": float(int(self._active.sum())),
            "pages_in_use": float(self._cache.pages_in_use),
            "pages_free": float(self._cache.pages_free),
            "slots_total": float(self.num_slots),
            "state_per_position_bytes": float(
                self._cache.bytes_per_position()),
            "state_per_slot_bytes": float(self._cache.bytes_per_slot()),
            "state_per_slot_sliding_bytes": float(
                self._cache.bytes_per_slot("sliding")),
            "state_per_slot_recurrent_bytes": float(
                self._cache.bytes_per_slot("recurrent")),
        }

    @property
    def alive(self) -> bool:
        """``False`` once the loop has crashed — the health probe's fast
        path for telling "this replica is dead" from "this replica is slow"."""
        with self._cv:
            return not self._crashed

    @property
    def draining(self) -> bool:
        """Whether admission is paused (explicit :meth:`drain` or an
        in-flight :meth:`hot_swap`)."""
        with self._cv:
            return self._draining or self._swap is not None

    # ------------------------------------------------- tier hooks (host side)

    def cancel(self, pending: _Pending) -> bool:
        """Abort a submitted request: queued — removed and resolved
        ``"aborted"`` immediately; in a slot — retired ``"aborted"`` at the
        loop's next iteration (slot and pages reclaimed).  Returns ``False``
        when the request had already finished.  This is what makes a 504 a
        *release* instead of a leak, and what makes router failover
        idempotent: once the cancelled handle resolves, this engine is
        provably no longer executing the request."""
        if pending.done():
            return False
        if self._queue.remove(pending):
            self._finish(pending, [], "aborted", 0.0)
            self._metrics["queue_depth"].set(len(self._queue))
            return True
        with self._cv:
            running = self._running
            if running:
                self._cancelled.append(pending)
                self._cv.notify_all()
        if not running and not pending.done():
            # no loop to process it (engine stopped or never started with
            # the handle outside the queue) — resolve it directly
            self._finish(pending, [], "aborted", 0.0)
        return True

    def drain(self, timeout: float = 30.0) -> bool:
        """Pause admission and wait until every occupied slot retires.
        Queued requests stay queued (they admit again after
        :meth:`resume`).  Returns ``True`` once drained; ``False`` on
        timeout (admission stays paused either way).  The wait is a
        ``serving.drain`` span, so a request that stalls behind a drain
        shows the interference on its critical path."""
        span = NOOP_SPAN
        if _truntime.enabled():
            span = _trace.span("serving.drain")
        with span:
            with self._cv:
                self._draining = True
                started = self._thread is not None
                self._cv.notify_all()
            if not started:
                return True  # no loop ⇒ nothing in flight, nothing can admit
            deadline = time.perf_counter() + timeout
            while time.perf_counter() < deadline:
                with self._cv:
                    running, acked = self._running, self._drain_ack
                if not running:
                    return True  # stopped/crashed under us — slots are clear
                if (acked and not self._active.any()
                        and not self._inflight):
                    return True  # nothing on the device, nothing unread
                time.sleep(0.002)
            return False

    def resume(self) -> None:
        """Reopen admission after :meth:`drain`."""
        with self._cv:
            self._draining = False
            self._drain_ack = False
            self._cv.notify_all()

    def hot_swap(self, model, params=None, timeout: float = 30.0) -> None:
        """Swap the served params in place — the checkpoint hot-swap.

        Geometry (the block's ``DecodeSpec.geometry`` and ``state``: widths,
        heads, max_len, vocab, depth) must match
        the engine's current spec: the decode step is param-*shape*-stable,
        so the swap reuses every compiled program — no retrace, no
        recompile.  The loop applies the swap at the first iteration with
        zero active slots (admission pauses until then): in-flight requests
        finish under the old params, queued requests decode under the new,
        and nothing drops.  With a draft model, only the target swaps — the
        verify step guarantees target-distribution samples under any draft,
        so acceptance rate may dip but correctness cannot.  The blocking
        window (geometry check through drain-and-apply) is a
        ``serving.hot_swap`` span — the other interference source a
        request's critical path can surface."""
        span = NOOP_SPAN
        if _truntime.enabled():
            span = _trace.span("serving.hot_swap")
        with span:
            self._hot_swap(model, params, timeout)

    def _hot_swap(self, model, params, timeout: float) -> None:
        new = _resolve_spec(model, params)
        if self._mesh is not None:
            new = self._sharded(new, type(model).__name__)
        with self._cv:
            old = self._spec
        if new.geometry != old.geometry or new.layout != old.layout:
            differ = [f"{a} != {b}" for a, b in zip(
                new.geometry + new.layout, old.geometry + old.layout)
                if a != b]
            raise ValueError(
                "hot_swap geometry mismatch: "
                + (", ".join(differ) or "another kind of block"))
        with self._cv:
            if self._crashed:
                raise EngineCrashed("engine crashed; cannot hot_swap")
            if self._swap is not None:
                raise RuntimeError("another hot_swap is already in flight")
            if not self._running:
                # no loop ⇒ no in-flight work: swap synchronously
                self._spec = new
                self._metrics["hot_swaps"].inc()
                return
            done = threading.Event()
            self._swap = (new, done)
            self._cv.notify_all()
        if not done.wait(timeout):
            with self._cv:
                self._swap = None
            raise TimeoutError(f"hot_swap did not drain within {timeout}s")

    @property
    def prefill_buckets(self) -> Tuple[int, ...]:
        return self._buckets

    # ------------------------------------------------------------ host loop

    def _loop(self) -> None:
        """The host loop.  It never blocks on the program it has just
        dispatched: an iteration dispatches its prefills (``_admit``) and its
        decode step, each fed by the device outputs of the program before,
        and only then reads the tokens of everything older than the newest
        program (``_read_behind``) while that one runs.  The host's own work
        (uploads, dispatch, read-back latency, bookkeeping, admission) then
        passes under device time.  Wherever the host must see the truth it
        reads everything first (``_flush``): before it sleeps, before a
        hot-swap is applied, before a cancel, on a crash and on the way
        out.

        The loop accounts for all of its own time, always (``_phase``): a
        pass that admitted or stepped is a ``serving.loop`` span, one that
        found nothing to do a ``serving.loop.idle`` (its read of what was in
        flight and its sleep), both numbered by ``iter``, and what it does
        inside lies under ``serving.loop.admit`` / ``.prefill`` /
        ``.dispatch`` / ``.wait`` / ``.emit``."""
        while self._iterate():
            pass

    def _phase(self, name: str, timed: Optional[str] = None, **attrs):
        """Open one phase of the loop thread's account of its own time: a
        span in the flight-recorder ring whether or not telemetry is on
        (``trace.loop_span``: it carries its iteration's ``iter``) and,
        where ``timed`` names a histogram, one observation of it from the
        span's own two clock reads, so that span and number cannot drift
        apart.  Attributes known only at the end go into the span's
        ``attrs`` before it closes."""
        return _trace.loop_span(
            name, observe=self._metrics[timed].observe if timed else None,
            **attrs)

    def _iterate(self) -> bool:
        """One pass of the loop; False when it was the last."""
        number = self._iter
        self._iter += 1
        gc_before = _trace.gc_seconds
        self._starved = 0
        span = self._phase("serving.loop", "loop_iteration", iter=number)
        with span:
            try:
                with self._cv:
                    running = self._running
                    self._drain_ack = self._draining
                    swap_pending = self._swap is not None
                    paused = self._draining or swap_pending
                if not running:
                    span.keep = bool(self._inflight)
                    self._flush()  # stop() gets back what the device made
                    return False
                self._cancel_requested()
                if swap_pending and not self._active.any():
                    self._flush()  # what is in flight ran on the old params
                    self._apply_swap()
                    with self._cv:
                        paused = self._draining
                admitted = 0 if paused else self._admit()
                if _chaos.enabled() and self._active.any():
                    # the kill_replica site: only busy iterations count, so
                    # a seeded kill always lands mid-decode with requests in
                    # flight (the failover path is what's under test)
                    _chaos.fault("replica")
                active = self._decode_once()
            except _chaos.ChaosKilled:
                self._crash()
                return False
            span.keep = progressed = bool(admitted or active)
            span.attrs.update(admitted=admitted, active=active,
                              starved=self._starved)
        if progressed:
            collecting_s = _trace.gc_seconds - gc_before
            if collecting_s:
                self._metrics["gc_pause"].inc(collecting_s)
            return True
        with self._phase("serving.loop.idle", "loop_idle", iter=number):
            self._slept = True  # nobody asked for anything: the next
            # dispatch finds the device empty and is not starved
            self._flush()  # nothing new to run behind: read it all
            with self._cv:
                if (self._running and self._swap is None
                        and not self._cancelled
                        and (paused or len(self._queue) == 0)):
                    self._cv.wait(timeout=0.05)
        return True

    def _cancel_requested(self) -> None:
        """Retire every slot whose request was cancelled (loop thread only)."""
        with self._cv:
            if not self._cancelled:
                return
            cancelled, self._cancelled = self._cancelled, []
        self._flush()  # a cancelled request hands back what the device made
        for pending in cancelled:
            if pending.done():
                continue
            if self._queue.remove(pending):
                self._finish(pending, [], "aborted", 0.0)
                continue
            for slot, state in enumerate(self._slots):
                if state is not None and state.pending is pending:
                    self._retire(slot, "aborted")
                    break
        self._metrics["queue_depth"].set(len(self._queue))

    def _apply_swap(self) -> None:
        """Apply a pending hot-swap (loop thread, zero active slots)."""
        with self._cv:
            if self._swap is None:
                return  # hot_swap timed out and withdrew the request
            spec, done = self._swap
            self._spec = spec
            self._swap = None
        self._metrics["hot_swaps"].inc()
        done.set()

    def _crash(self) -> None:
        # Runs ON the loop thread after a chaos kill — the in-process
        # analogue of the replica's process dying mid-decode.  Every
        # in-flight and queued request aborts (partial tokens included) and
        # the engine refuses further work; the tier's probe sees alive=False
        # and its router fails the aborted requests over.
        with self._cv:
            self._crashed = True
            self._running = False
            self._thread = None
            self._cv.notify_all()
        self._flush()  # the partial tokens are those the device made
        for slot in range(self.num_slots):
            if self._slots[slot] is not None:
                self._retire(slot, "aborted")
        while True:
            pending = self._queue.pop()
            if pending is None:
                break
            self._finish(pending, [], "aborted", 0.0)
        self._metrics["queue_depth"].set(0)

    def _admit(self) -> int:
        """Move queued requests into free slots (prefill); how many it
        moved.  FIFO with head-of-line blocking: when the page pool can't
        fit the next request yet, it waits for a retirement rather than
        being skipped — no starvation of big requests."""
        admitted = 0
        with self._phase("serving.loop.admit") as span:
            while True:
                free = [i for i, st in enumerate(self._slots) if st is None]
                if not free:
                    break
                pending = self._queue.pop()
                if pending is None:
                    break
                need = self._cache.pages_needed(
                    len(pending.request.prompt) + pending.max_new
                )
                if not self._cache.can_alloc(need):
                    self._queue.requeue_front(pending)
                    break
                self._prefill_into(free[0], pending, need)
                admitted += 1
            self._metrics["queue_depth"].set(len(self._queue))
            # an engine with nothing to run writes its idle span and no other
            span.keep = bool(admitted) or bool(self._active.any())
            span.attrs["admitted"] = admitted
        return admitted

    def _next_seq(self) -> int:
        """Count the program about to be dispatched and give it its ``seq``,
        its place in the engine's order of dispatch (on one stream, the
        device's order of execution).  The dispatch is *starved* when it
        finds the device empty while the host worked: the newest unread
        program is already finished (one non-blocking ``is_ready()`` of its
        tokens, which no program donates), or none is unread although the
        loop has not slept for want of requests since its last dispatch."""
        if self._inflight:
            starved = self._inflight[-1].tok.is_ready()
        else:
            starved = not self._slept
        self._slept = False
        self._metrics["dispatches"].inc()
        if starved:
            self._metrics["dispatches_starved"].inc()
            self._starved += 1
        self._seq += 1
        return self._seq - 1

    def _prefill_into(self, slot: int, pending: _Pending, need: int) -> None:
        """Dispatch one prefill into ``slot`` and do not wait for it: the
        program seats its first token and its key into the decode step's
        inputs on the device, and the token is read one program behind
        (``_first_token``), where ``ttft_s`` is stamped.  The speculative
        engine's loop is serial and reads it at once."""
        req = pending.request
        plen = len(req.prompt)
        # smallest bucket that fits the prompt (the ladder always ends at
        # max_context and submit bounded plen, so next() can't exhaust)
        width = next(w for w in self._buckets if w >= plen)
        serial = self._draft_spec is not None
        with self._phase("serving.loop.prefill", slot=slot, width=width,
                         plen=plen) as phase:
            self._cache.alloc(slot, need)
            t0 = time.perf_counter()
            self._metrics["queue_wait"].observe(t0 - pending.enqueue_t)
            span = NOOP_SPAN
            if _truntime.enabled():
                # the loop thread serves every request, so the ids ride span
                # args (no thread-bound context here); queue wait spans the
                # gap between the admission thread's enqueue and this prefill
                _trace.record(
                    "serving.queue_wait", pending.enqueue_t, t0,
                    request_id=req.request_id, trace_id=req.trace_id,
                    parent="serving.admit")
                attrs: Dict[str, Any] = dict(
                    request_id=req.request_id, trace_id=req.trace_id,
                    parent="serving.admit", slot=slot, width=width, plen=plen)
                if req.tenant:
                    attrs["tenant"] = req.tenant
                span = _trace.span("serving.prefill", **attrs)
            state = _SlotState(pending, plen)
            state.pages = need
            with span:
                tokens = np.zeros((1, width), np.int32)
                tokens[0, :plen] = req.prompt
                tokens_dev = jnp.asarray(tokens)
                # a copy: the host goes on writing the table while this runs
                table = jnp.asarray(self._cache.tables[
                    slot, : width // self._cache.page_size].copy())
                last, keys = ((self._last, self._keys) if serial
                              else (self._dev["last"], self._dev["keys"]))
                key = jax.random.PRNGKey(req.seed)
                seq = phase.attrs["seq"] = self._next_seq()
                tok, last, keys, *aux = self._keep(
                    self._cache, self._prefill_for(width)(
                        self._spec.params(), *self._pools_of(self._cache),
                        tokens_dev, table, np.int32(plen), key,
                        np.float32(req.temperature), np.int32(req.top_k),
                        np.float32(req.top_p), last, keys, np.int32(slot)))
                spec_on = serial and req.speculative is not False
                if spec_on:
                    dc = self._draft_cache
                    self._next_seq()
                    self._keep(dc, self._prefill_for(width, role="draft")(
                        self._draft_spec.params(), *self._pools_of(dc),
                        tokens_dev, table))
                    # a draft chain decorrelated from the request's target
                    # chain
                    self._draft_keys[slot] = np.asarray(
                        jax.random.fold_in(jax.random.PRNGKey(req.seed), 7))
                if serial:
                    # np.array: a writable host copy
                    self._keys = np.array(keys)
                else:
                    self._dev["last"], self._dev["keys"] = last, keys
            now = time.perf_counter()
            self._metrics["prefill_seconds"].observe(now - t0)
            self._metrics["prefill_padded"].inc(width - plen)

            state.admit_t = now
            self._slots[slot] = state
            self._pos[slot] = plen
            self._temp[slot] = req.temperature
            self._topk[slot] = req.top_k
            self._topp[slot] = req.top_p
            # an answer of one token takes no decode step: its slot only
            # waits for the read
            self._active[slot] = state.steps_left > 0
            self._spec_on[slot] = spec_on
            self._dirty = True
            self._inflight.append(_InFlight(
                tok, [(slot, state)], seq,
                prefill=(t0 - pending.enqueue_t, now - t0),
                aux=aux[0] if aux else None))
            self._refresh_gauges()
        if serial:
            self._flush()
            if self._slots[slot] is state:
                self._last[slot] = state.tokens[0]

    def _decode_once(self) -> int:
        """One engine iteration over every active slot: a plain decode
        step dispatched ahead of the last one's read, or (with a draft
        model, serially) m draft steps + one verify step.  How many slots
        it stepped: 0 when none is active."""
        active = int(self._active.sum())
        if not active:
            return 0
        if self._draft_spec is not None:
            self._spec_once(active)
        else:
            self._plain_once(active)
        return active

    def _step_span(self):
        """A ``serving.decode_step`` span for one engine iteration.  One
        jitted step serves every active slot, so attribution is a *list* of
        request ids (``args.requests``); when a single request — or a
        single trace — is active, the scalar ``request_id``/``trace_id``
        are promoted too so per-request tooling joins without list
        handling.  NOOP when telemetry is off (no list building either)."""
        if not _truntime.enabled():
            return NOOP_SPAN
        reqs = [self._slots[i].pending.request
                for i in range(self.num_slots)
                if self._active[i] and self._slots[i] is not None]
        attrs: Dict[str, Any] = {
            "requests": [r.request_id for r in reqs],
            "n_active": len(reqs),
        }
        traces = sorted({r.trace_id for r in reqs if r.trace_id})
        if len(reqs) == 1:
            attrs["request_id"] = reqs[0].request_id
            attrs["parent"] = "serving.prefill"
        if len(traces) == 1:
            attrs["trace_id"] = traces[0]
        elif traces:
            attrs["trace_ids"] = traces
        tenants = sorted({r.tenant for r in reqs if r.tenant})
        if len(tenants) == 1:
            attrs["tenant"] = tenants[0]
        elif tenants:
            attrs["tenants"] = tenants
        return _trace.span("serving.decode_step", **attrs)

    def _count_kv_read(self, pos) -> None:
        """How far the bound by live length engages, from what the host
        already knows (the traced programs do not change with it): the
        positions one single-token step is told to cover, each active slot's
        ``pos + 1`` rounded up to the block, against every slot's whole
        window."""
        blocks = -(-(pos[self._active] + 1) // self._kv_block)
        self._metrics["kv_read"].inc(
            int(np.minimum(blocks * self._kv_block, self._width).sum()))
        self._metrics["kv_capacity"].inc(self.num_slots * self._width)

    def _step_inputs(self) -> Tuple[Any, ...]:
        """The decode step's eight per-slot inputs, as device arrays.  What
        an admit or a retire changed is uploaded again, in one transfer and
        as copies (the host goes on writing its mirrors while the step is in
        flight); ``last``, ``keys`` and, on a steady step, ``pos`` are the
        outputs of the program before."""
        dev = self._dev
        if self._dirty:
            active = self._active.copy()
            # a slot that takes no part writes its row to the scratch page
            tables = self._cache.tables * active[:, None]
            (dev["tables"], dev["pos"], dev["temp"], dev["top_k"],
             dev["top_p"], dev["active"]) = jax.device_put(
                (tables, self._pos * active, self._temp.copy(),
                 self._topk.copy(), self._topp.copy(), active))
            # the path the program will choose from these same arrays
            self._level = int(sampling_level(
                self._temp, self._topk, self._topp, active, self._vocab))
            self._dirty = False
        return (dev["tables"], dev["pos"], dev["last"], dev["keys"],
                dev["temp"], dev["top_k"], dev["top_p"], dev["active"])

    def _plain_once(self, active: int) -> None:
        """Dispatch the next decode step, then read what is behind it.  The
        step's token, position and key inputs are the device outputs of the
        program before (no host round trip); the host advances its mirror
        of ``pos`` by count and releases a slot whose last step this was
        (its end by length is a count the host has), so no step is spent on
        a finished slot.  Only then are the tokens of the step before and of
        this iteration's prefills read, while this step runs.  ``eos_id`` is
        therefore seen one step late: that slot rides this step too and its
        overrun token is dropped.  The call's wall time (dispatch of this
        step, wait for and read-back of the previous) is one observation of
        ``serving_token_latency_seconds``; ``serving.loop.dispatch`` is its
        first part, up to where the reading begins."""
        t0 = time.perf_counter()
        with self._step_span():
            with self._phase("serving.loop.dispatch", "loop_dispatch",
                             active=active) as phase:
                self._count_kv_read(self._pos)
                uploaded = self._dirty
                inputs = self._step_inputs()
                seq = self._next_seq()
                tok, pos, keys, *aux = self._keep(self._cache, self._decode(
                    self._spec.params(), *self._pools_of(self._cache),
                    *inputs))
                self._dev.update(last=tok, pos=pos, keys=keys)
                self._metrics["decode_steps"].inc()
                if self._level >= 1:
                    self._metrics["decode_sampled"].inc()
                if self._level >= 2:
                    self._metrics["decode_sorted"].inc()
                if any(rec.prefill is None for rec in self._inflight):
                    # the step before is unread: this one took its outputs
                    self._metrics["decode_chained"].inc()
                rows = [(int(slot), self._slots[slot])
                        for slot in np.flatnonzero(self._active)]
                self._inflight.append(_InFlight(
                    tok, rows, seq, aux=aux[0] if aux else None))
                self._pos[self._active] += 1
                for slot, state in rows:
                    state.steps_left -= 1
                    if state.steps_left <= 0:
                        self._release(slot)
                phase.attrs.update(seq=seq, uploaded=uploaded,
                                   level=self._level)
            self._read_behind(1, t0)
        self._metrics["token_latency"].observe(time.perf_counter() - t0)

    # ---------------------------------------------- reading one program behind

    def _read_behind(self, keep: int, t0: float) -> None:
        """Read the tokens of every program in flight but the newest
        ``keep``, oldest first, blocking on each until the device has made
        them (``serving.loop.wait``: the host's slack), then hand them to
        their requests (``serving.loop.emit``).  ``t0`` is when the caller's
        own work began: the ledger's share of the loop's time for a step."""
        while len(self._inflight) > keep:
            # still in flight until its tokens are with their requests: a
            # drain that sees nothing in flight may tell its caller so
            rec = self._inflight[0]
            step = rec.prefill is None
            with self._phase("serving.loop.wait", "loop_wait", seq=rec.seq,
                             kind="step" if step else "prefill"):
                toks = np.asarray(rec.tok)  # device sync: that program is done
            with self._phase("serving.loop.emit", "loop_emit", seq=rec.seq,
                             rows=len(rec.rows)) as phase:
                now = time.perf_counter()
                resolved = self._resolved
                if rec.aux is not None:
                    self._observe(
                        jax.tree.map(np.asarray, rec.aux),
                        len(rec.rows) if step else rec.rows[0][1].plen, step)
                if step:
                    self._step_tokens(rec, toks, now - t0)
                else:
                    self._first_token(rec, int(toks), now)
                self._inflight.popleft()
                phase.attrs["finished"] = self._resolved - resolved

    def _flush(self) -> None:
        """Read everything in flight: the host sees what the device made."""
        self._read_behind(0, time.perf_counter())

    def _first_token(self, rec: _InFlight, tok0: int, now: float) -> None:
        (slot, state), = rec.rows
        pending = state.pending
        state.ttft_s = now - pending.enqueue_t  # the token is on the host
        self._metrics["ttft"].observe(state.ttft_s)
        if self._ledger is not None:
            # prompt tokens, queue wait, prefill device-seconds, and the
            # first sampled token bill with the first token — all
            # host-visible
            queue_wait_s, device_s = rec.prefill
            self._ledger.admit(
                pending.request.tenant, prompt_tokens=state.plen,
                queue_wait_s=queue_wait_s, device_s=device_s, generated=1)
        self._emit(slot, state, tok0)

    def _step_tokens(self, rec: _InFlight, toks, dt: float) -> None:
        ledger = self._ledger
        # device-seconds estimate: the loop's time for the step split evenly
        # over the slots it decoded for
        share = dt / max(1, len(rec.rows))
        for slot, state in rec.rows:
            if state.done:
                continue  # retired since (EOS a step late, a cancel): dropped
            if ledger is not None:
                ledger.decode(state.pending.request.tenant,
                              tokens=1, device_s=share)
            self._emit(slot, state, int(toks[slot]))

    def _emit(self, slot: int, state: _SlotState, t: int) -> None:
        """Hand one read token to its request; finish it at EOS or at its
        length.  The slot is the request's still unless its last step was
        dispatched before (``_plain_once``)."""
        state.tokens.append(t)
        self._metrics["tokens"].inc()
        eos = state.pending.request.eos_id
        if eos is not None and t == eos:
            reason = "eos"
        elif len(state.tokens) >= state.pending.max_new:
            reason = "length"
        else:
            return
        if self._slots[slot] is state:
            self._release(slot)
        self._resolve(state, reason)

    def _spec_once(self, active_n: int) -> None:
        """One speculative iteration: chain m draft steps (device arrays
        flow straight between dispatches — no host syncs), verify the
        window in one target step, then emit each slot's accepted prefix.
        The same three phases as the plain loop's, around serial steps: the
        ``serving.loop.dispatch`` holds all m + 1 programs and carries the
        verify's ``seq``, whose tokens the ``wait`` blocks on."""
        t0 = time.perf_counter()
        m = self._spec_tokens
        with self._step_span():
            with self._phase("serving.loop.dispatch", "loop_dispatch",
                             active=active_n, uploaded=True,
                             level=2) as phase:
                tables = jnp.asarray(self._cache.tables)
                temp = jnp.asarray(self._temp)
                topk = jnp.asarray(self._topk)
                topp = jnp.asarray(self._topp)
                active = jnp.asarray(self._active)
                base_pos = self._pos
                last = jnp.asarray(self._last)
                dkeys = jnp.asarray(self._draft_keys)
                dc = self._draft_cache
                dparams = self._draft_spec.params()
                drafts, qprobs = [], []
                for i in range(m):
                    self._count_kv_read(base_pos + i)
                    self._next_seq()
                    tok, qp, _, dkeys = self._keep(dc, self._draft_step(
                        dparams, *self._pools_of(dc), tables,
                        jnp.asarray(base_pos + i), last, dkeys, temp, topk,
                        topp, active))
                    drafts.append(tok)
                    qprobs.append(qp)
                    last = tok
                inputs = (
                    tables, jnp.asarray(base_pos), jnp.asarray(self._last),
                    tuple(drafts), tuple(qprobs), jnp.asarray(self._keys),
                    temp, topk, topp, active, jnp.asarray(self._spec_on))
                seq = phase.attrs["seq"] = self._next_seq()
                out, count, accepted, keys = self._keep(
                    self._cache, self._verify(
                        self._spec.params(), *self._pools_of(self._cache),
                        *inputs))
            with self._phase("serving.loop.wait", "loop_wait", seq=seq,
                             kind="step"):
                out = np.asarray(out)   # device sync: the iteration is done
                counts = np.asarray(count)
                acc = np.asarray(accepted)
        with self._phase("serving.loop.emit", "loop_emit", seq=seq,
                         rows=active_n) as phase:
            resolved = self._resolved
            self._spec_emit(out, counts, acc, keys, dkeys,
                            time.perf_counter() - t0)
            phase.attrs["finished"] = self._resolved - resolved

    def _spec_emit(self, out, counts, acc, keys, dkeys, dt: float) -> None:
        """Hand a verified window's accepted tokens to their requests."""
        m = self._spec_tokens
        self._keys = np.array(keys)
        self._draft_keys = np.array(dkeys)
        self._metrics["token_latency"].observe(dt)
        self._metrics["decode_steps"].inc()
        # the verify program judges every window under the modified
        # distributions: the sort, whatever the knobs
        self._metrics["decode_sampled"].inc()
        self._metrics["decode_sorted"].inc()
        spec_slots = self._active & self._spec_on
        n_spec = int(spec_slots.sum())
        if n_spec:
            self._metrics["spec_proposed"].inc(m * n_spec)
            self._metrics["spec_accepted"].inc(int(acc[spec_slots].sum()))
        ledger = self._ledger
        share = dt / max(1, int(self._active.sum()))

        for slot in range(self.num_slots):
            state = self._slots[slot]
            if state is None or not self._active[slot]:
                continue
            req = state.pending.request
            if ledger is not None and spec_slots[slot]:
                # accepted + rejected = m per spec slot, so the tenant sums
                # conserve against serving_spec_{proposed,accepted}_total
                accepted = int(acc[slot])
                ledger.speculative(req.tenant, accepted=accepted,
                                   rejected=m - accepted)
            retired = False
            emitted = 0
            for j in range(int(counts[slot])):
                t = int(out[slot, j])
                state.tokens.append(t)
                emitted += 1
                self._metrics["tokens"].inc()
                if req.eos_id is not None and t == req.eos_id:
                    self._retire(slot, "eos")
                    retired = True
                    break
                if len(state.tokens) >= state.pending.max_new:
                    self._retire(slot, "length")
                    retired = True
                    break
            if ledger is not None:
                ledger.decode(req.tenant, tokens=emitted, device_s=share)
            if not retired:
                self._pos[slot] += emitted
                self._last[slot] = int(out[slot, emitted - 1])

    def _release(self, slot: int) -> None:
        """Give the slot and its pages back (loop thread): after the
        request's last step was dispatched, or with its retirement.  The
        device's program order keeps a later prefill into these pages behind
        every step that still writes them."""
        state = self._slots[slot]
        if self._ledger is not None:
            # page-seconds sample at slot free: pages held x wall time
            self._ledger.release(
                state.pending.request.tenant, pages=state.pages,
                held_s=time.perf_counter() - state.admit_t)
        self._cache.free(slot)
        self._slots[slot] = None
        self._active[slot] = False
        self._spec_on[slot] = False
        self._pos[slot] = 0
        self._last[slot] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._topp[slot] = 1.0
        self._dirty = True
        self._refresh_gauges()

    def _retire(self, slot: int, reason: str) -> None:
        state = self._slots[slot]
        self._release(slot)
        self._resolve(state, reason)

    def _resolve(self, state: _SlotState, reason: str) -> None:
        state.done = True
        self._resolved += 1
        self._finish(state.pending, state.tokens, reason, state.ttft_s)

    def _finish(self, pending: _Pending, tokens: List[int], reason: str,
                ttft_s: float) -> None:
        self._metrics["requests"].inc()
        pending._resolve(GenerateResult(
            request_id=pending.request.request_id,
            prompt=list(pending.request.prompt),
            tokens=list(tokens),
            finish_reason=reason,
            ttft_s=ttft_s,
            latency_s=time.perf_counter() - pending.enqueue_t,
            trace_id=pending.request.trace_id,
        ))

    def _refresh_gauges(self) -> None:
        self._metrics["active_slots"].set(int(self._active.sum()))
        self._metrics["pages_in_use"].set(self._cache.pages_in_use)
