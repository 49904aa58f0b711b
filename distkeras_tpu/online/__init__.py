"""Online learning loop — close the serve→train circle on one fleet.

The train→serve half of continuous learning already exists (verified
checkpoint publication + the serving tier's rolling hot-swap, ROADMAP item
5); this package adds the missing serve→train half, so one fleet serves,
captures what it served, retrains on it, and hot-swaps to the result —
continuously, and provably under fault injection:

* :class:`~distkeras_tpu.online.capture.TrafficLog` — bounded in-memory
  ring over served generations, journal-backed for bitwise crash resume,
  rotated into :class:`~distkeras_tpu.datapipe.MemmapSource`-compatible
  ``.npy`` replay shards published atomically with per-window manifests
  (tmp + fsync + ``os.replace``, per-file sha256 — the checkpoint
  discipline applied to data);
* :class:`~distkeras_tpu.online.capture.SamplingPolicy` — deterministic
  sampling rate, content filter, and per-tenant window quotas so one hot
  client cannot dominate a retrain window;
* :class:`~distkeras_tpu.online.scheduler.WindowScheduler` — polls for
  published windows and closes each into retrain → verified checkpoint
  publish (+ :class:`~distkeras_tpu.datapipe.DataState` sidecar) → the
  serving tier's watcher rolls the fleet, zero dropped requests;
* :func:`~distkeras_tpu.online.scheduler.plan_placement` — capacity-aware
  trainer/replica placement over live fleet leases, recorded by the
  daemon's ``online_loop`` / ``online_status`` / ``stop_online`` verbs
  (:mod:`distkeras_tpu.job_deployment`);
* :func:`~distkeras_tpu.online.capture.online_metrics` — the ``online_*``
  flightdeck schema (window lag, samples ingested / dropped-by-quota,
  swap age), pinned by ``tests/golden/online_metrics.txt``.

Wire it up in-process::

    from distkeras_tpu import online, serving
    log = online.TrafficLog(capture_dir, window_samples=256,
                            policy=online.SamplingPolicy(tenant_quota=64))
    serving.install_http_endpoint(engine, traffic_log=log)   # capture
    sched = online.WindowScheduler(capture_dir, train_fn, ckpt_dir)
    tier.watch_checkpoints(ckpt_dir, loader)                 # hot-swap
    sched.start()                                            # retrain

or as a daemon deployment: ``Job.online_loop(replicas=3, ...)`` spawns the
serving tier and the scheduler loop as co-scheduled jobs on one fleet.
"""

from distkeras_tpu.online.capture import (
    SamplingPolicy,
    TrafficLog,
    load_window_manifest,
    online_metrics,
    published_windows,
    verify_window,
    window_manifest_path,
    window_source,
)
from distkeras_tpu.online.scheduler import WindowScheduler, plan_placement

__all__ = [
    "SamplingPolicy",
    "TrafficLog",
    "WindowScheduler",
    "load_window_manifest",
    "online_metrics",
    "plan_placement",
    "published_windows",
    "verify_window",
    "window_manifest_path",
    "window_source",
]
