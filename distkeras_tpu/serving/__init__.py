"""Online inference: continuous batching, paged per-layer state, SLO metrics.

The request-level serving layer ROADMAP item 1 calls for — everything the
training side can only do call-at-a-time (``greedy_generate``) reshaped for
a service that admits requests whenever they arrive:

* :class:`~distkeras_tpu.serving.engine.ServingEngine` — the decode loop
  (fixed slot ring, ONE jitted step, prefill-on-admission / retire-on-EOS).
  It serves any model whose ``decode_spec(params)`` returns a
  :class:`~distkeras_tpu.models.decode.DecodeSpec` (the block contract: the
  kinds of state a layer keeps, which layer keeps which, and the model's
  own embedding, prefill layer, step layer and head): ``TransformerLM``,
  ``StagedLM``, ``LatentMoELM``, ``ShortcutMoELM``, ``WindowMoELM``;
* :mod:`~distkeras_tpu.serving.cache` — the paged cache: slot page tables
  over shared pools, one tuple of per-layer pools for each kind of state the
  block declares (keys and values, multi-head or grouped-query; or latent
  attention's one row for all heads, written by an expanded prefill and
  read by an absorbed step), and for a kind that a window bounds (a
  sliding-window layer's keys and values) a ring of ``window`` rows a slot
  instead of pages;
* :mod:`~distkeras_tpu.serving.sampling` — temperature / top-k / top-p
  with per-request seeds, all traced (no recompiles);
* :mod:`~distkeras_tpu.serving.frontend` — request/response dataclasses,
  bounded queue with backpressure, the flightdeck ``/generate`` endpoint;
* :mod:`~distkeras_tpu.serving.tier` — the fault-tolerant router over N
  replicas: health-gated least-loaded dispatch, failover retry, deadline
  propagation, load shedding, rolling checkpoint hot-swap.

Serve over HTTP (flightdeck exporter carries the endpoint)::

    from distkeras_tpu import serving
    engine = serving.ServingEngine(trained_model)
    serving.install_http_endpoint(engine)      # POST/GET /generate
    # SLO histograms (serving_ttft_seconds, serving_token_latency_seconds,
    # serving_queue_depth, ...) appear on the same server's /metrics.

or as a daemon job: ``PunchcardServer``'s ``serve`` verb
(:mod:`distkeras_tpu.job_deployment`), which forwards engine knobs via
``Job.serve(flags=...)`` -> :func:`serve_flags`.

Fast paths (all optional engine kwargs): ``prefill_buckets`` — power-of-two
prefill width ladder; ``draft_model``/``spec_tokens`` — speculative
decoding with exact accept/resample semantics; ``mesh`` — tensor-parallel
decode over the local devices.  The last two are builds a block opts into
(``DecodeSpec.window`` / ``.shard``): GPT-2's block brings both,
the three expert blocks neither yet, and the engine refuses those
combinations at construction.
"""

from distkeras_tpu.serving.cache import (
    PagedKVCache,
    append_rows,
    paged_decode_attention,
    rollback_rows,
    sliding_decode_attention,
)
from distkeras_tpu.serving.engine import EngineCrashed, ServingEngine, serving_metrics
from distkeras_tpu.serving.frontend import (
    GenerateRequest,
    GenerateResult,
    QueueFull,
    RequestQueue,
    install_http_endpoint,
    serve_flags,
)
from distkeras_tpu.serving.sampling import (
    modified_probs,
    sample_one,
    sample_tokens,
    speculative_verify,
)
from distkeras_tpu.serving.tier import (
    HttpReplica,
    LocalReplica,
    ReplicaDead,
    ServingTier,
    TierDeadline,
    TierError,
    TierExhausted,
    TierSaturated,
    install_tier_endpoint,
    tier_metrics,
    watch_and_swap,
)

__all__ = [
    "EngineCrashed",
    "GenerateRequest",
    "GenerateResult",
    "HttpReplica",
    "LocalReplica",
    "PagedKVCache",
    "QueueFull",
    "ReplicaDead",
    "RequestQueue",
    "ServingEngine",
    "ServingTier",
    "TierDeadline",
    "TierError",
    "TierExhausted",
    "TierSaturated",
    "append_rows",
    "install_http_endpoint",
    "install_tier_endpoint",
    "modified_probs",
    "paged_decode_attention",
    "sliding_decode_attention",
    "rollback_rows",
    "sample_one",
    "sample_tokens",
    "serve_flags",
    "serving_metrics",
    "speculative_verify",
    "tier_metrics",
    "watch_and_swap",
]
