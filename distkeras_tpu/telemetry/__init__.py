"""``distkeras_tpu.telemetry`` — spans, metrics, and profiler hooks.

One subsystem, three surfaces:

* :mod:`.trace` — ``trace.span("epoch")`` context managers exporting Chrome
  trace-event JSON (open in Perfetto);
* :mod:`.metrics` — process-global registry of counters/gauges/histograms
  with Prometheus-text, JSONL, and ScalarLogger exporters, plus
  ``jax.monitoring`` compile hooks;
* :mod:`.profiler` — step-windowed ``jax.profiler`` capture via
  ``DISTKERAS_PROFILE=dir``;
* :mod:`.flightdeck` — live HTTP scrape (``DISTKERAS_TELEMETRY_HTTP``),
  flight-recorder ring with crash blackbox dumps, and the fleet ``run_id``
  stamped into every trace event and scrape.

**Always recorded**: the spans of a loop's own iteration — the training
loop's (``epoch``, ``epoch_arrays``, ``h2d``, ``h2d_transfer``, ``dispatch``,
``device_epoch``, ``stats_wait``), a handful an epoch, and the serving
loop's (``serving.loop`` and its ``.admit`` / ``.prefill`` / ``.dispatch`` /
``.wait`` / ``.emit``, ``serving.loop.idle``), some seven an iteration — and
a ``gc`` span for a garbage collection that held the interpreter over a
millisecond, into the flight-recorder ring with their absolute
``perf_counter`` times (what that costs, the readiness thread's wake-ups
included: :mod:`.trace`).
**Governed by** ``DISTKERAS_TELEMETRY`` (see :mod:`.runtime`): everything
dear — every other ``trace.span()`` (a shared no-op when unset), the trace
and metrics files, histograms, the HTTP scrape, the crash blackbox.
**Nothing blocks** either way: no span waits for the device on the thread
that opens it, so instrumented code dispatches the same programs in the
same order with the flag set or unset.  Import cost is stdlib-only; jax is
touched lazily.
"""

from __future__ import annotations

import os

from distkeras_tpu.telemetry import accounting, dynamics, flightdeck, runtime
from distkeras_tpu.telemetry.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    install_jax_hooks,
    metrics,
)
from distkeras_tpu.telemetry.profiler import ProfilerHook
from distkeras_tpu.telemetry.runtime import configure, enabled, out_dir
from distkeras_tpu.telemetry.trace import Span, Tracer, trace

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "ProfilerHook",
    "Registry",
    "Span",
    "Tracer",
    "accounting",
    "configure",
    "dynamics",
    "enabled",
    "flightdeck",
    "flush",
    "install_jax_hooks",
    "metrics",
    "out_dir",
    "runtime",
    "trace",
]


def flush(directory=None):
    """Write the trace and a metrics snapshot to ``directory`` (default:
    :func:`out_dir`).  Returns ``(trace_path, metrics_path)``, or ``None``
    when telemetry is disabled."""
    if not enabled():
        return None
    d = directory or out_dir()
    os.makedirs(d, exist_ok=True)
    pid = os.getpid()
    extra = {"pid": pid}
    rid = flightdeck.current_run_id()
    if rid is not None:
        extra["run_id"] = rid
    trace_path = trace.write(os.path.join(d, f"trace_{pid}.json"))
    metrics_path = metrics.write_jsonl(
        os.path.join(d, f"metrics_{pid}.jsonl"), extra=extra
    )
    return trace_path, metrics_path
