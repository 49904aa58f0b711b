"""Driver for traffic of kind ``train_job``: one call of the public entry
point, ``dk.<Trainer>(...).train(df)``, measured from outside.

The window opens when epoch 0 (which compiles, or reads the compile cache) has
finished on the device and closes when the last epoch has; both edges come
from the watcher's reading of ``trainer.num_updates``.  ``train()`` cannot be
stopped, so the number of epochs is fixed before the call from the cell's
``rate_hint``: on the code the hint was taken on, the window lasts
``--seconds``.  Telemetry stays off and no XLA flag is set here.
"""

import importlib
import math
import os
import shutil
import tempfile
import threading
import time

import checks
import harness
import tracelib
from watcher import Watcher, epoch_times

#: the capture begins this share of an epoch's period before an epoch is due
#: to end, and stops when two more epochs have ended: one whole epoch with the
#: gap before it, and as little else as can be.  Stopping the profiler is
#: dear, some 6 s and 0.1 ms for every operation event (10 s for 1.5 s of the
#: GPT-2 job, 25-38 s for 3.9 s: my chip runs, PR 23), so ISSUE 23's 3 s and
#: two whole epochs cannot be afforded.  The CNN job's program makes 155k
#: operation events a second, and a whole epoch of it came back with 4% of
#: the program's events missing (18.5 s to stop after 1.27 s).  A cell with
#: such a program names ``capture_s`` in its file: the stop is then asked that
#: many seconds after the epoch has ended, so the capture holds the end of one
#: epoch program, the gap in which the host prepares the next, and the
#: beginning of the next (12 s to stop after 0.6 s, nothing missing); if the
#: device recorded more, the reducer takes what it finds.
TRACE_LEAD = 0.2
#: fewest epochs a job is given, however short ``--seconds``: epoch 0 compiles,
#: epochs 1 and 2 give the period, epochs 3 and 4 end inside the capture
MIN_EPOCHS = 5


def plan(cell, seconds):
    """The job's sizes, from the cell's files alone."""
    config, traffic = cell["config_spec"], cell["traffic_spec"]
    training = config["training"]
    workers = traffic["trainer_kwargs"]["num_workers"]
    windows = training["windows_per_worker_per_epoch"]
    rows = (workers * windows * training["trainer_kwargs"]["communication_window"]
            * training["trainer_kwargs"]["batch_size"])
    items = rows * config["items_per_row"]
    epochs = max(MIN_EPOCHS,
                 1 + math.ceil(seconds * cell["rate_hint"] / items))
    return {"workers": workers, "windows_per_epoch": windows,
            "commits_per_epoch": workers * windows, "rows_per_epoch": rows,
            "items_per_epoch": items, "epochs": epochs}


def _target(path):
    module, _, name = path.partition(":")
    return getattr(importlib.import_module(module), name)


def build_trainer(cell, job, seed):
    """The trainer exactly as a user would build it, from the files."""
    import distkeras_tpu as dk
    from distkeras_tpu.models import FlaxModel

    config, traffic = cell["config_spec"], cell["traffic_spec"]
    training = config["training"]
    module = _target(config["model"]["import"])(**config["model"]["kwargs"])
    optimizer = dict(training["optimizer"]["knobs"])
    if training["optimizer"]["learning_rate_over_workers"]:
        # DOWNPOUR adds every worker's delta to the center, so the worker's
        # rate is the configuration's over the worker count
        # (chip_smoke.py::worker_optimizer)
        optimizer["learning_rate"] /= job["workers"]
    kwargs = dict(training["trainer_kwargs"])
    kwargs.update(traffic["trainer_kwargs"])
    return getattr(dk, traffic["trainer"])(
        FlaxModel(module),
        worker_optimizer=(training["optimizer"]["name"], optimizer),
        metrics=(), num_epoch=job["epochs"], seed=seed, **kwargs)


class CompileClock:
    """JAX's own trace, lower and backend-compile (or cache-read) durations,
    each stamped with the host's clock when it ended (as in
    ``chip_smoke.py::compile_clock``)."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events = []  # (t_end, event, seconds)
        self.cache_hits = 0

    def _duration(self, event, seconds, **_):
        if event.startswith("/jax/core/compile/"):
            self.events.append((time.perf_counter(), event, seconds))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *_):
        import jax.monitoring as monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


class TraceWindow:
    """Captures one whole epoch with the profiler, or with ``capture_s`` the
    boundary between two, on a thread of its own so that neither the training
    thread nor the watcher waits for it.  After three epochs have ended, the
    period between the last two says when the next is due; the capture begins
    TRACE_LEAD of a period before that.  A whole-epoch capture stops when two
    more epochs have ended; a boundary capture ``capture_s`` seconds after
    the first has (or, if none ends within two leads, then)."""

    def __init__(self, log_dir, commits_per_epoch, capture_s=None):
        self.log_dir = log_dir
        self._per_epoch = commits_per_epoch
        self._capture_s = capture_s
        self._ended = []  # when each epoch was seen to have ended
        self._over = False
        self._wake = threading.Condition()
        self.error = None
        self.stopped = False
        self.first_epoch = None  # index of the first epoch to end inside
        self._thread = threading.Thread(target=self._run, name="bench-tracer",
                                        daemon=True)
        self._thread.start()

    def on_count(self, count, t):
        with self._wake:
            self._ended += [t] * (count // self._per_epoch - len(self._ended))
            self._wake.notify_all()

    def _wait(self, ready, until=None):
        """True once ``ready()``; False if the job ended (or ``until`` came)
        first."""
        with self._wake:
            while not ready():
                left = 0.5 if until is None else until - time.perf_counter()
                if self._over or left <= 0:
                    return False
                self._wake.wait(min(0.5, left))
            return True

    def _sleep(self, until):
        self._wait(lambda: False, until=until)

    def _run(self):
        import jax

        try:
            if not self._wait(lambda: len(self._ended) >= 3):
                return
            period = self._ended[-1] - self._ended[-2]
            lead = TRACE_LEAD * period
            self._sleep(self._ended[-1] + period - lead)
            if self._over:
                return
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the watcher's loop would fill it
            # the host tracer is off: at levels 1 and 2 the runtime's 13
            # transfer threads record a million ``Transpose`` events a second
            # during the CNN job's host-to-device relayout, and the gap they
            # work in grows from 0.13 s to 0.54-0.87 s (my chip runs, PR 23):
            # the tracer would measure itself.  So no host event labels a gap
            options.host_tracer_level = 0
            begun = time.perf_counter()
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            started, first = time.perf_counter(), len(self._ended)
            if self._capture_s is None:
                self._wait(lambda: len(self._ended) >= first + 2)
            else:
                self._wait(lambda: len(self._ended) > first,
                           until=started + 2 * lead)
                self._sleep(time.perf_counter() + self._capture_s)
            asked, inside = time.perf_counter(), len(self._ended) - first
            jax.profiler.stop_trace()
            self.stopped, self.first_epoch = True, first
            harness.note(profiler={
                "start_took_s": started - begun, "captured_s": asked - started,
                "stop_took_s": time.perf_counter() - asked,
                "epochs_ended_inside": inside})
        except BaseException as error:
            self.error = error

    def finish(self):
        with self._wake:
            self._over = True
            self._wake.notify_all()
        self._thread.join(240.0)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")
        if self.error is not None:
            raise self.error
        return tracelib.find_xplane(self.log_dir) if self.stopped else None


def run(*, cell, seed, seconds, trace, t_start, device, keep_trace=None):
    """``keep_trace``: a directory to copy the profiler's files into, for a
    look by hand (``run.py --keep-trace``)."""
    import jax

    import distkeras_tpu as dk

    config = cell["config_spec"]
    job = plan(cell, seconds)
    maker = harness.resolve(".", config["data"]["maker"])
    x, y = maker(job["rows_per_epoch"], seed, **config["data"]["kwargs"])
    t_data = time.perf_counter()
    trainer = build_trainer(cell, job, seed)
    frame = dk.from_numpy(x, y)
    watcher = Watcher(lambda: trainer.num_updates)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = None
    try:
        if trace:
            tracer = TraceWindow(trace_dir, job["commits_per_epoch"],
                                 cell.get("capture_s"))
            watcher.callbacks.append(tracer.on_count)
        with CompileClock() as clock:
            watcher.start()
            try:
                trainer.train(frame, **cell["traffic_spec"].get("train_kwargs", {}))
            finally:
                stamps = watcher.stop()
        xplane = tracer.finish() if tracer else None
        commits = int(trainer.num_updates)
        losses = [float(v) for v in trainer.get_history().get("loss", [])]
        reduced = tracelib.reduce_file(xplane) if xplane else None
    finally:
        if trace_dir:
            if keep_trace and os.path.isdir(trace_dir):
                shutil.copytree(trace_dir, os.path.join(
                    keep_trace, cell["name"]), dirs_exist_ok=True)
            shutil.rmtree(trace_dir, ignore_errors=True)

    done = epoch_times(stamps, job["commits_per_epoch"], job["epochs"])
    expected = job["commits_per_epoch"] * job["epochs"]
    correct, failed, reasons = checks.judge_training(
        losses, job["epochs"], commits, expected, config["correct"])
    if done[0] is None or done[-1] is None:
        raise SystemExit("no window: the watcher never saw the first or the "
                         f"last epoch complete; {reasons}")
    setup_s, window_s = done[0] - t_start, done[-1] - done[0]
    items = (job["epochs"] - 1) * job["items_per_epoch"]
    throughput = items / window_s / cell["chips"]
    memory = [d.memory_stats() or {} for d in jax.local_devices()]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in memory)
    flops_per_item = harness.resolve(".", config["flops"]["function"])(
        **config["flops"]["kwargs"])

    harness.note(job=job, loss_per_epoch=losses, commits=commits,
                 expected_commits=expected, correct=correct, reasons=reasons)
    harness.note(epoch_completions_s=[None if t is None else t - done[0]
                                      for t in done],
                 window_s=window_s, data_s=t_data - t_start,
                 setup_s=setup_s, cache_hits=clock.cache_hits,
                 train_s=trainer.get_training_time(), memory_stats=memory[0])

    device = dict(device, memory_peak_bytes=memory_peak)
    breakdown = None
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = reduced["breakdown"]
        harness.note(trace={k: v for k, v in reduced.items()
                            if k not in ("breakdown", "named_s")})
    facts = {"cell": cell, "job": job, "epoch_done": done,
             "window": (done[0], done[-1]), "throughput": throughput,
             "flops_per_item": flops_per_item,
             "peaks": harness.peaks_for(device["kind"]),
             "compile_events": clock.events, "compile_backend": clock.BACKEND,
             "memory_peak_bytes": memory_peak, "trace": reduced,
             "traced_epoch": tracer and tracer.first_epoch}
    return {"correct": correct, "attempted": job["epochs"], "failed": failed,
            "end_to_end": {"setup_s": setup_s,
                           "train_throughput": throughput},
            "device": device, "facts": facts, "breakdown": breakdown}
