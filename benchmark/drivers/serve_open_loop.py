"""Driver for traffic of kind ``serve_open_loop``: one ``ServingEngine`` in
this process, requests through ``engine.submit``, measured from outside.

Set-up makes the weights from the seed (``reference_lm.make_weights``), builds
the engine with the traffic file's ``engine_kwargs`` and a registry of its
own, and sends one request through every prefill width that the run's prompts
use and through the decode step; ``setup_s`` ends when the last of them has
come back.  Then the schedule starts: ``ramp_s`` seconds of traffic, then the
window of ``--seconds``.  ``arrival: "poisson"`` sends on the schedule from
one sender thread whether or not earlier requests have finished, and takes
every latency from the instant a request was due; ``arrival: "closed"`` has
``clients`` threads that each send their next request when their last has
come back.  Requests due (sent, in a closed loop) inside the window are
``attempted``.  When the window has closed the run waits for them (a late
answer is late, not wrong), stops the engine, reads the device's memory,
frees the engine and only then runs the plain reference over the finished
requests: that comparison decides ``correct``
(``servechecks.py``).  Telemetry and accounting stay off.

The first token's instant is the engine's own stamp (``GenerateResult.ttft_s``
after the benchmark's stamp of the submit): the engine streams nothing, so
only it sees that instant.  A request's end is stamped here, by the thread
that waits for it, and a note line says how far the engine's own latency
lies from it.
"""

import importlib
import itertools
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

import harness
import reference_lm
import servechecks
import servegen
import tracelib

#: seconds between two readings of ``engine.stats()``
SAMPLE_S = 0.05
#: how long after the window's close an answer is still waited for
DRAIN_S = 60.0
#: the profiler's capture, from the middle of the window (host tracer off, as
#: in ``train_job.py``, whose readings of what stopping costs apply here too),
#: unless the cell's file names ``capture_s``: the reducer's slice runs from
#: the end of the first decode step in the capture to the end of the last, so
#: a capture has to hold three steps or more
CAPTURE_S = 1.0
#: the registry's instruments that the readers take, by the engine's names
HISTOGRAMS = ("serving_token_latency_seconds", "serving_prefill_seconds")
COUNTERS = ("serving_prefill_padded_tokens", "serving_tokens_total")


def plan(cell, seconds):
    """The schedule's sizes, from the cell's files alone."""
    traffic = cell["traffic_spec"]
    return {"arrival": traffic["arrival"], "ramp_s": traffic["ramp_s"],
            "requests": servegen.request_count(traffic, seconds),
            "rate_per_s": traffic.get("rate_per_s"),
            "clients": traffic.get("clients")}


def check_cell(cell, run_seconds):
    """What this kind asks of a cell's files (``selftest.py files``): an
    arrival kind with its sizes, laws that the generator knows, the serving
    group of the configuration with its rule of ``correct``."""
    traffic, config = cell["traffic_spec"], cell["config_spec"]
    model = config["model"]["kwargs"]
    assert plan(cell, run_seconds)["requests"] >= 1
    assert traffic["ramp_s"] >= 0 and traffic["engine_kwargs"]["num_slots"] >= 1
    if traffic["arrival"] == "poisson":
        # the knee of one sweep on the chip and the cell's rate, as numbers
        assert 0 < traffic["rate_per_s"] <= traffic["knee_per_s"]
        assert traffic["rate_from"]
    servegen.schedule(dict(traffic, rate_per_s=1.0, clients=2,
                           requests_per_client=2), 1, 2.0,
                      vocab=model["vocab_size"], max_len=model["max_len"])
    serving = config["serving"]
    assert harness.resolve(".", serving["flops"]["function"])(
        prompt=3, generated=2, **serving["flops"]["kwargs"]) > 0
    rules = serving["correct"]
    assert rules["requests"] >= 1 and rules["reason"]
    assert all(rules[name + "_at_most"] > 0 for name in servechecks.COMPARED)


def _target(path):
    module, _, name = path.partition(":")
    return getattr(importlib.import_module(module), name)


def p90(values):
    """The ninth of nine cut points, interpolated between the sorted values
    (as ``readers/host.py::epoch_ms_p90``)."""
    values = list(values)
    if len(values) < 2:
        return values[0] if values else None
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def finished_inside(records, t_open, t_close):
    """The records whose answer came back inside ``[t_open, t_close)``."""
    return [r for r in records if r["done"] is not None
            and r["error"] is None and t_open <= r["done"] < t_close]


def generated_inside(record, t_open, t_close):
    """How many of a finished request's tokens were made inside ``[t_open,
    t_close)``.  The engine streams nothing, so a request has two stamps, its
    first token's and its end's: the first token counts where its instant
    falls, the others are spread evenly from there to the end (each decode
    step gives every active slot one token)."""
    first = record["sent"] + record["ttft_s"]
    count = 1.0 if t_open <= first < t_close else 0.0
    if record["tokens"] > 1 and record["done"] > first:
        overlap = min(record["done"], t_close) - max(first, t_open)
        count += (record["tokens"] - 1) * max(0.0, overlap) / (
            record["done"] - first)
    return count if record["tokens"] else 0.0


def summarise(records, t_open, t_close, deadline):
    """The window's arithmetic on finished records.  A record is a dict with
    ``due`` (the instant it was due; in a closed loop the instant it was
    sent), ``sent``, ``done`` (this driver's stamps; ``done`` None where no
    answer came), ``ttft_s`` (the engine's, from ``sent``), ``tokens`` (how
    many came back), ``max_new``, ``reason`` and ``error``.

    ``attempted``: due inside ``[t_open, t_close)``.  ``failed``: refused
    (``error``), no answer by ``deadline``, a finish other than ``length`` or
    a wrong token count.  A request that was refused or never answered is in
    every tail with the deadline's wait (first token, latency, and that wait
    a token too), so that it misses any limit and a system that drops
    requests cannot read better for the lighter load.  ``ttft`` runs from
    ``due`` to the first token, ``tpot`` is ``(latency - ttft) / (tokens -
    1)``, ``per_token`` is the whole latency from ``due`` over the tokens:
    the wait for a slot is in it.  ``generated_per_s`` is the tokens made
    inside the window (``generated_inside``, over every answered request, the
    ramp's too) over its seconds: all the work of the window and no other.
    ``tokens_per_s`` counts the whole answers that ended inside it instead
    (what ISSUE 26 named: it takes in what the requests in flight at the
    opening had made before, and leaves out what those in flight at the close
    made inside, and so swings with the order of the work), and
    ``processed_per_s`` those answers' prompt and generated tokens."""
    window = [r for r in records if t_open <= r["due"] < t_close]
    ttft, tpot, latency, per_token = [], [], [], []
    failed, unanswered = 0, 0
    for r in window:
        whole = (r["done"] is not None and r["reason"] == "length"
                 and r["tokens"] == r["max_new"])
        failed += r["error"] is not None or not whole
        unanswered += r["error"] is None and not whole
        if r["error"] is not None or r["done"] is None:
            for tail in (ttft, latency, tpot, per_token):
                tail.append(deadline - r["due"])
            continue
        first = r["sent"] + r["ttft_s"] - r["due"]
        ttft.append(first)
        latency.append(r["done"] - r["due"])
        if r["tokens"] > 1:
            tpot.append((latency[-1] - first) / (r["tokens"] - 1))
        if r["tokens"]:
            per_token.append(latency[-1] / r["tokens"])
    finished = finished_inside(records, t_open, t_close)
    seconds = t_close - t_open
    ms = lambda values, pick: 1e3 * pick(values) if values else None
    return {"attempted": len(window), "failed": failed,
            "unanswered": unanswered,
            "completed_in_window": len(finished),
            "generated_per_s": sum(
                generated_inside(r, t_open, t_close) for r in records
                if r["done"] is not None and r["error"] is None) / seconds,
            "tokens_per_s": sum(r["tokens"] for r in finished) / seconds,
            "processed_per_s": sum(r["tokens"] + r["prompt_tokens"]
                                   for r in finished) / seconds,
            "ttft_p50_ms": ms(ttft, statistics.median),
            "ttft_p90_ms": ms(ttft, p90),
            "tpot_p50_ms": ms(tpot, statistics.median),
            "tpot_p90_ms": ms(tpot, p90),
            "per_token_p50_ms": ms(per_token, statistics.median),
            "per_token_p90_ms": ms(per_token, p90),
            "latency_p50_ms": ms(latency, statistics.median),
            "latency_p90_ms": ms(latency, p90)}


class Sampler:
    """Reads ``engine.stats()`` every SAMPLE_S on a thread of its own, and
    the registry's instruments at the window's two edges."""

    def __init__(self, engine, registry, t_open, t_close):
        self._engine, self._registry = engine, registry
        self._edges = {"open": t_open, "close": t_close}
        self.samples = []   # (t, stats) inside the window
        self.marks = {}     # edge -> the instruments' readings there
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-sampler")

    def instruments(self):
        read = {}
        for name in HISTOGRAMS:
            histogram = self._registry.histogram(name)
            read[name] = (histogram.sum, histogram.count)
        for name in COUNTERS:
            read[name] = self._registry.counter(name).value
        return read

    def start(self):
        self._thread.start()
        return self

    def _loop(self):
        t_open, t_close = self._edges["open"], self._edges["close"]
        tick = 0
        while not self._stop.is_set():
            self._stop.wait(max(0.0, min(t_close, t_open + tick * SAMPLE_S)
                                - time.perf_counter()))
            now = time.perf_counter()
            for edge, at in self._edges.items():
                if edge not in self.marks and now >= at:
                    self.marks[edge] = self.instruments()
            if now >= t_close:
                return
            if now >= t_open:
                self.samples.append((now, self._engine.stats()))
            tick = max(tick + 1, int((now - t_open) / SAMPLE_S) + 1)

    def stop(self):
        """Ends the thread; a window that closed a moment ago (a closed loop's
        clients return at its close) is read here if the thread had not."""
        self._stop.set()
        self._thread.join(10.0)
        if "open" in self.marks and "close" not in self.marks:
            self.marks["close"] = self.instruments()
        self._engine = self._registry = None  # keep nothing of the engine alive


class Capture:
    """CAPTURE_S of the profiler from the middle of the window, on a thread
    of its own (python and host tracers off)."""

    def __init__(self, log_dir, at, seconds):
        self.log_dir, self._at, self._seconds = log_dir, at, seconds
        self.error, self.stopped = None, False
        self._over = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-tracer")
        self._thread.start()

    def _run(self):
        import jax

        try:
            if self._over.wait(max(0.0, self._at - time.perf_counter())):
                return
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            begun = time.perf_counter()
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            started = time.perf_counter()
            self._over.wait(self._seconds)
            asked = time.perf_counter()
            jax.profiler.stop_trace()
            self.stopped = True
            harness.note(profiler={
                "start_took_s": started - begun, "captured_s": asked - started,
                "stop_took_s": time.perf_counter() - asked})
        except BaseException as error:  # surfaced by finish(); never swallowed
            self.error = error

    def finish(self):
        self._over.set()
        self._thread.join(240.0)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")
        if self.error is not None:
            raise self.error
        return tracelib.find_xplane(self.log_dir) if self.stopped else None


class Load:
    """Sends the schedule and keeps one record a request."""

    def __init__(self, engine, requests, t0, t_open, t_close):
        from distkeras_tpu.serving import GenerateRequest, QueueFull

        self._request, self._full = GenerateRequest, QueueFull
        self._engine, self._requests = engine, requests
        self.t0, self.t_open, self.t_close = t0, t_open, t_close
        self.deadline = t_close + DRAIN_S
        self.records, self.late_s = [], []
        self._lock = threading.Lock()
        self._threads = []

    def _send(self, item, due):
        """Submit one request now; its record (the answer is waited for by
        the caller or by a thread of its own)."""
        record = {"index": item["index"], "due": due, "max_new": item["max_new"],
                  "prompt_tokens": len(item["prompt"]), "done": None,
                  "ttft_s": None, "tokens": 0, "reason": None, "error": None,
                  "engine_latency_s": None, "served": None}
        with self._lock:
            self.records.append(record)
        record["sent"] = time.perf_counter()
        try:
            pending = self._engine.submit(self._request(
                prompt=item["prompt"], max_new_tokens=item["max_new"],
                temperature=0.0, eos_id=None))
        except self._full:
            record["error"] = "queue_full"
            return record, None
        return record, pending

    def _wait(self, record, pending):
        result = pending.result(
            timeout=max(0.0, self.deadline - time.perf_counter()))
        if result is not None:
            record.update(done=time.perf_counter(), ttft_s=result.ttft_s,
                          tokens=len(result.tokens), served=result.tokens,
                          reason=result.finish_reason,
                          engine_latency_s=result.latency_s)

    def _sender(self):
        horizon = self.t_close - self.t0
        for item in self._requests:
            if item["due_s"] >= horizon:
                continue
            due = self.t0 + item["due_s"]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.late_s.append(time.perf_counter() - due)
            record, pending = self._send(item, due)
            if pending is not None:
                waiter = threading.Thread(target=self._wait, daemon=True,
                                          args=(record, pending))
                waiter.start()
                self._threads.append(waiter)

    def _client(self, items):
        for item in itertools.cycle(items):  # a fast engine: start over
            now = time.perf_counter()
            if now >= self.t_close:
                return
            record, pending = self._send(item, now)
            if pending is None:
                time.sleep(0.01)  # refused: the next one a little later
                continue
            self._wait(record, pending)
            if record["done"] is None:
                return

    def start(self, traffic):
        if traffic["arrival"] == "poisson":
            threads = [threading.Thread(target=self._sender, daemon=True,
                                        name="bench-sender")]
        else:
            threads = [threading.Thread(
                target=self._client, daemon=True, name=f"bench-client-{c}",
                args=([r for r in self._requests if r["client"] == c],))
                for c in range(traffic["clients"])]
        self._senders = threads
        for thread in threads:
            thread.start()
        return self

    def finish(self):
        """Wait for every sender and every answer, at most until the deadline
        (each waits with that timeout itself)."""
        for thread in self._senders:
            thread.join(max(1.0, self.deadline + 5.0 - time.perf_counter()))
        for thread in list(self._threads):
            thread.join(max(1.0, self.deadline + 5.0 - time.perf_counter()))
        alive = [t.name for t in self._senders + self._threads if t.is_alive()]
        self._engine = None  # the pools must be free to go once it is stopped
        if alive:
            raise RuntimeError(f"load threads did not end: {alive[:5]}")


def warm_up(engine, widths, max_len, vocab, seed):
    """One request through each of ``widths`` (prefill) and one decode step
    behind it; compiles or reads the cache."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    for width in widths:
        length = min(width, max_len - 2)
        prompt = rng.integers(0, vocab, size=length).tolist()
        result = engine.generate(prompt, max_new_tokens=2, timeout=300.0)
        if len(result.tokens) != 2:
            raise SystemExit(f"warm-up at width {width} returned "
                             f"{len(result.tokens)} tokens")


def say_compared(compared):
    """Each number compared beside its limit, as the last lines on standard
    error."""
    for name, entry in compared.items():
        print(f"compared {name}: {entry['value']!r} limit {entry['limit']!r}",
              file=sys.stderr, flush=True)


def build(cell, seed, seconds):
    """Weights and requests from the seed, and an engine on a registry of
    its own: ``(weights, requests, registry, engine, prefill widths)``."""
    from distkeras_tpu.serving import ServingEngine
    from distkeras_tpu.telemetry.metrics import Registry

    for flag in ("DISTKERAS_TELEMETRY", "DISTKERAS_ACCOUNTING"):
        if os.environ.get(flag):
            raise SystemExit(f"{flag} is set: the benchmark measures with "
                             "telemetry and accounting off")
    config, traffic = cell["config_spec"], cell["traffic_spec"]
    model = config["model"]["kwargs"]
    weights = reference_lm.make_weights(seed, **model)
    requests = servegen.schedule(traffic, seed, seconds,
                                 vocab=model["vocab_size"],
                                 max_len=model["max_len"])
    registry = Registry()
    engine = ServingEngine(_target(config["model"]["import"])(**model), weights,
                           registry=registry, **traffic["engine_kwargs"])
    widths = sorted({next(w for w in engine.prefill_buckets
                          if w >= len(r["prompt"])) for r in requests})
    return weights, requests, registry, engine, widths


def drive(engine, registry, requests, traffic, seconds, trace_dir=None,
          capture_s=CAPTURE_S):
    """The ramp and the window on a warm engine: ``(load, sampler, the
    capture's file or None)``.  Returns when every answer is back or its
    deadline has passed; the engine is left running."""
    t0 = time.perf_counter()
    t_open = t0 + traffic["ramp_s"]
    t_close = t_open + seconds
    sampler = Sampler(engine, registry, t_open, t_close).start()
    capture = trace_dir and Capture(
        trace_dir, t_open + (seconds - capture_s) / 2, capture_s)
    load = Load(engine, requests, t0, t_open, t_close).start(traffic)
    try:
        load.finish()
    finally:
        sampler.stop()
        xplane = capture.finish() if capture else None
    return load, sampler, xplane


def run(*, cell, seed, seconds, trace, t_start, device, keep_trace=None):
    import jax

    config, traffic = cell["config_spec"], cell["traffic_spec"]
    model, serving = config["model"]["kwargs"], config["serving"]
    compile_clock = harness.load_driver("train_job").CompileClock
    weights, requests, registry, engine, widths = build(cell, seed, seconds)
    built_s = time.perf_counter() - t_start
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with compile_clock() as clock:
            try:
                warm_up(engine, widths, model["max_len"], model["vocab_size"],
                        seed)
                setup_s = time.perf_counter() - t_start
                load, sampler, xplane = drive(
                    engine, registry, requests, traffic, seconds, trace_dir,
                    cell.get("capture_s", CAPTURE_S))
            finally:
                engine.stop(timeout=30.0)
        reduced = tracelib.reduce_file(xplane) if xplane else None
    finally:
        if trace_dir:
            if keep_trace and os.path.isdir(trace_dir):
                shutil.copytree(trace_dir, os.path.join(
                    keep_trace, cell["name"]), dirs_exist_ok=True)
            shutil.rmtree(trace_dir, ignore_errors=True)
    t_open, t_close = load.t_open, load.t_close

    memory = [d.memory_stats() or {} for d in jax.local_devices()]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in memory)
    del engine  # the pools go before the reference runs

    records = load.records
    summary = summarise(records, t_open, t_close, load.deadline)
    finished = [r for r in records if r["done"] is not None
                and t_open <= r["due"] < t_close]
    clock_gap = [abs((r["done"] - r["sent"]) - r["engine_latency_s"])
                 for r in finished]
    late = sorted(load.late_s)
    harness.note(
        plan=plan(cell, seconds), prefill_widths=widths, summary=summary,
        sender_late_ms=late and {
            "p50": 1e3 * statistics.median(late),
            "p99": 1e3 * late[min(len(late) - 1, int(0.99 * len(late)))],
            "max": 1e3 * late[-1]},
        engine_latency_off_by_ms_max=clock_gap and 1e3 * max(clock_gap),
        setup_s=setup_s, built_s=built_s, cache_hits=clock.cache_hits,
        memory_stats=memory[0])

    in_use_before_check = (jax.local_devices()[0].memory_stats() or {}).get(
        "bytes_in_use")  # the pools are gone: the weights and little else
    t_check = time.perf_counter()
    compared, reasons, also_read = servechecks.judge_serving(
        weights, requests, finished, seed, serving["correct"],
        width=model["max_len"])
    if summary["unanswered"]:
        reasons.append(f"{summary['unanswered']} requests never came back, "
                       "or with the wrong number of tokens")
    if not summary["attempted"]:
        reasons.append("no request was due inside the window")
    correct = not reasons
    harness.note(correct=correct, reasons=reasons, compared=compared,
                 also_read=also_read, check_s=time.perf_counter() - t_check,
                 bytes_in_use_before_check=in_use_before_check)

    flops_of = harness.resolve(".", serving["flops"]["function"])
    in_window = finished_inside(records, t_open, t_close)
    processed = sum(r["tokens"] + r["prompt_tokens"] for r in in_window)
    flops = sum(flops_of(prompt=r["prompt_tokens"], generated=r["tokens"],
                         **serving["flops"]["kwargs"]) for r in in_window)
    device = dict(device, memory_peak_bytes=memory_peak)
    breakdown = None
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = reduced["breakdown"]
        harness.note(trace={k: v for k, v in reduced.items()
                            if k not in ("breakdown", "named_s")})
    facts = {"cell": cell, "window": (t_open, t_close),
             "throughput": summary["processed_per_s"] / cell["chips"],
             "flops_per_item": flops / processed if processed else 0.0,
             "peaks": harness.peaks_for(device["kind"]),
             "compile_events": clock.events, "compile_backend": clock.BACKEND,
             "memory_peak_bytes": memory_peak, "trace": reduced,
             # no epochs here: ``readers/device.py`` asks for these two
             "traced_epoch": None, "epoch_done": [],
             "summary": summary, "samples": sampler.samples,
             "marks": sampler.marks,
             # prompts whose prefill ended (the first token's instant) inside
             "prefilled_prompt_tokens": sum(
                 r["prompt_tokens"] for r in records if r["ttft_s"] is not None
                 and t_open <= r["sent"] + r["ttft_s"] < t_close)}
    # the manifest says which of these a cell reports
    end_to_end = {"setup_s": setup_s, "tpot_p90_ms": summary["tpot_p90_ms"],
                  "serve_tokens_per_s": (summary["generated_per_s"]
                                         / cell["chips"])}
    say_compared(compared)
    return {"correct": correct, "attempted": summary["attempted"],
            "failed": summary["failed"], "end_to_end": end_to_end,
            "device": device, "facts": facts, "breakdown": breakdown,
            "compared": compared}
