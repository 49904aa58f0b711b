"""Span tracer exporting Chrome trace-event JSON (Perfetto-loadable).

Usage at a host boundary (never inside jitted code):

    with telemetry.trace.loop_span("epoch", epoch=3):
        with telemetry.trace.span("window_dispatch", window=0):
            ...

Spans clock with ``time.perf_counter`` (monotonic — DK106's whole point),
nest per-thread, and are recorded as complete ("ph": "X") events whose
ts/dur containment gives Perfetto the nesting; each event also carries an
explicit ``args.parent`` so tests and scripts need no interval math.

**What is always recorded.**  The spans of a loop's own iteration, opened
with :meth:`Tracer.loop_span` or closed by :meth:`Tracer.probe`, go into the
flight-recorder ring whether or not ``DISTKERAS_TELEMETRY`` is set: two
``perf_counter`` reads and a tuple store each on the thread that opens them,
and the readiness thread's wake-ups (below).  The ring keeps, for every span,
its name, its absolute ``perf_counter`` start and end in seconds, the thread,
``parent``, and the index that the spans of one iteration share
(``flightdeck.recorder.spans()``).  Three kinds:

* the per-epoch training loop's ``epoch``, ``epoch_arrays``, ``h2d``,
  ``h2d_transfer``, ``dispatch``, ``device_epoch``, ``stats_wait``: a
  handful an epoch, sharing ``epoch``;
* the serving loop's (``serving/engine.py``): ``serving.loop`` (one
  iteration that admitted or stepped: ``iter``, ``admitted``, ``active``,
  ``starved``), and beneath it ``serving.loop.admit``,
  ``serving.loop.prefill``, ``serving.loop.dispatch``, ``serving.loop.wait``
  (the loop thread blocked on the device: the host's slack) and
  ``serving.loop.emit``; ``serving.loop.idle`` for an iteration that found
  nothing to do (its last read and its sleep).  Some seven an iteration, all
  on the loop thread, sharing ``iter``; the programs' spans carry ``seq``,
  the engine's count of the programs it has dispatched, in dispatch order,
  which on one stream is the device's order of execution: the end of a
  ``serving.loop.wait`` is the moment the host saw the end of program
  ``seq``.  At 100-170 programs a second the ring (2048 entries) is a busy
  engine's last two to three seconds;
* ``gc``: a garbage collection of any generation that held the interpreter
  for over a millisecond (:meth:`Tracer.watch_gc`: ``generation``,
  ``collected``), on whatever thread ran it and under whatever span was open
  there.  It enters the ring with the next span of a loop that does (a
  collection can begin while its thread holds the ring's lock).

**What the switch governs** is what is
dear: every other ``span()`` (per-window, per-request), the tracer's own
unbounded event list and the files written from it, the ``phase_*``
histograms, the HTTP scrape.  With the switch off ``span()`` returns a shared
no-op context manager — one cached-bool check and one dict-free branch,
which the test suite pins against plain dict-lookup cost.

**Nothing blocks.**  No span waits for the device on the thread that opens
it.  A span that ends when device work completes (``h2d_transfer``: the rows
are on the device; ``device_epoch``: the epoch's losses are ready) is handed
to :meth:`Tracer.probe`: one daemon thread of this module asks the arrays
whether they are ready, records the span when they are, and drops its
reference.  It waits for nothing and dispatches nothing, so telemetry on and
off dispatch the same programs in the same order.  **What that thread costs**,
switch or no switch, in every training process: while it holds a probe (a
training loop's ``device_epoch`` is held all epoch long) it wakes every 20 ms
(``PROBE_IDLE_POLL_S``), and every millisecond (``PROBE_POLL_S``) once a
span has run nine tenths of the shorter of the last two of its name, or from
its start where that is under 0.2 s (``PROBE_LONG_S``) or unknown; a wake-up
takes the GIL for one ``is_ready()`` per array held.  In a steady loop of
long epochs that is some 50 wake-ups a second, 1000 over the last tenth of a
span, and a span dated at most 1 ms late; a span much shorter than the two
before it is dated at most 20 ms late, once.  A
probe whose span could not be recorded (the queue was full, or its arrays
raised) is counted in :attr:`Tracer.probes_lost`, which ``/healthz`` shows and
the benchmark's ``feed_gap`` note line carries: an epoch with a lost probe
has no ``h2d_transfer`` or ``device_epoch``, and the readers' medians skip it.

A span with ``phase="step"`` (or data/h2d/commit/...) additionally feeds the
``phase_<name>_seconds`` histogram in the global metrics registry on exit,
with telemetry on — the ``metrics_<pid>.jsonl`` snapshot's phase breakdown.
``h2d_transfer`` feeds ``h2d`` and ``device_epoch`` feeds ``step`` (the
device's epoch, not a host wait); the in-memory path feeds no ``commit``:
one fused program has no such boundary.  The streaming path's per-window
``window_h2d`` is the enqueue alone and feeds no phase.

Exceptions raised while recording are NOT swallowed: the CI tier-1 variant
with ``DISTKERAS_TELEMETRY=1`` exists precisely so instrumentation bugs fail
the build instead of silently disabling observability.

**Request tracing.**  A serving request crosses threads and processes
(router dispatch thread → replica HTTP handler → engine loop), so thread
nesting alone cannot stitch its spans together.  :meth:`Tracer.bind` binds a
``trace_id``/``request_id`` context to the current thread; every span the
thread records while bound carries those ids in its args (explicit span
attrs win).  Threads that do work *for* a request without a bound context —
the engine loop serves many requests per decode step — stamp the ids as
explicit span args instead.  ``tools/dktrace critical-path`` joins on them.
"""

from __future__ import annotations

import collections
import gc
import json
import os
import queue
import threading
import time
import uuid

from distkeras_tpu.telemetry import runtime
from distkeras_tpu.telemetry.flightdeck import correlate as _correlate
from distkeras_tpu.telemetry.flightdeck.recorder import recorder as _flight_recorder
from distkeras_tpu.telemetry.metrics import metrics as _registry

__all__ = ["NOOP_SPAN", "Span", "Tracer", "new_trace_id", "trace"]


def new_trace_id() -> str:
    """A fresh 32-hex trace id (the distributed-trace correlation key —
    minted once at the first hop that sees the request, reused by every
    later hop)."""
    return uuid.uuid4().hex


class _NoopSpan:
    """Shared do-nothing context manager for the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NOOP_SPAN = _NoopSpan()


class _ContextBinding:
    """Context manager installing a trace context on the current thread;
    restores the previous binding on exit (bindings nest — an inner bind
    layers over, and restores, the outer one)."""

    __slots__ = ("_tracer", "_ctx", "_prev")

    def __init__(self, tracer, ctx):
        self._tracer = tracer
        self._ctx = ctx

    def __enter__(self):
        tls = self._tracer._tls
        self._prev = getattr(tls, "ctx", None)
        tls.ctx = self._ctx
        return self._ctx

    def __exit__(self, exc_type, exc, tb):
        self._tracer._tls.ctx = self._prev
        return False


class Span:
    """Context manager recording one complete trace event on exit."""

    __slots__ = ("_tracer", "name", "phase", "attrs", "_t0")

    def __init__(self, tracer, name, phase, attrs):
        self._tracer = tracer
        self.name = name
        self.phase = phase
        self.attrs = attrs

    def __enter__(self):
        self._tracer._push(self.name)
        self._t0 = self._tracer._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = self._tracer._clock()
        parent = self._tracer._pop()
        self._tracer._record(self.name, self._t0, t1, parent, self.attrs,
                             self.phase)
        return False


#: the attributes that number a loop's iterations: the training loop's and
#: the serving loop's
ITERATION_KEYS = ("epoch", "iter")


class _LoopSpan(Span):
    """A span of a loop's own iteration (:meth:`Tracer.loop_span`).  One
    that is given ``epoch=`` or ``iter=`` makes it the thread's current
    iteration while it is open; one that is not takes the current one as its
    own, so that a span opened layers below the loop (``data.epoch_arrays``,
    ``engine.shard_batches``, the serving loop's phases) carries the
    identifier its iteration's other spans share.  ``observe``, if given, is
    called with the span's seconds when it is recorded: the same two clock
    reads as the span's own.  A span whose opener sets ``keep`` false before
    it closes leaves no record (an iteration that found nothing to do);
    ``attrs`` may be filled in until then."""

    __slots__ = ("_outer", "_observe", "keep")

    def __init__(self, tracer, name, phase, attrs, observe=None):
        super().__init__(tracer, name, phase, attrs)
        self._observe = observe
        self.keep = True

    def __enter__(self):
        tls = self._tracer._tls
        self._outer = outer = getattr(tls, "iteration", None)
        attrs = self.attrs
        for key in ITERATION_KEYS:
            if key in attrs:
                tls.iteration = (key, attrs[key])
                break
        else:
            if outer is not None:
                attrs[outer[0]] = outer[1]
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        tracer = self._tracer
        t1 = tracer._clock()
        parent = tracer._pop()
        tracer._tls.iteration = self._outer
        if self.keep:
            tracer._record_loop(self.name, self._t0, t1, parent, self.attrs,
                                self.phase)
            if self._observe is not None:
                self._observe(t1 - self._t0)
        return False


#: a collection that held the interpreter longer than this leaves a ``gc``
#: span (every collection counts in ``Tracer.gc_seconds``)
GC_SPAN_S = 1e-3


#: probes the readiness thread may hold unfinished; one more is dropped
PROBE_DEPTH = 64
#: how often the readiness thread looks at a span that is about to end: a
#: span it closes ends at most this much late
PROBE_POLL_S = 0.001
#: how often it looks while every span it holds has, by the last two of its
#: name, most of its length before it: the most a span that ends early is late
PROBE_IDLE_POLL_S = 0.02
#: the share of that length after which the looks come every PROBE_POLL_S
PROBE_NEAR = 0.9
#: a span expected to be shorter than this is looked at every PROBE_POLL_S
#: from its start: an idle poll would be a large part of it
PROBE_LONG_S = 0.2


def _ready(arrays) -> bool:
    import jax

    return all(leaf.is_ready() for leaf in jax.tree.leaves(arrays)
               if hasattr(leaf, "is_ready"))


def _poll_wait(dues, now):
    """How long the readiness thread may sleep: for ever with nothing held;
    else until the first span is due to be looked at closely, but at least
    one fine poll and at most one idle poll."""
    if not dues:
        return None
    return min(PROBE_IDLE_POLL_S, max(PROBE_POLL_S, min(dues) - now))


class _ReadinessProbe:
    """The thread that watches device arrays become ready so that no other
    has to wait for them.

    ``submit`` never waits: with ``PROBE_DEPTH`` probes unfinished it drops
    the new one and counts it in ``lost``.  The thread dispatches nothing to
    the device and waits for nothing either: it asks each array it holds
    whether it ``is_ready()``, records the span ``t0 -> ready`` of those
    that are, and drops its reference to them at once.  (Blocking on them in
    the order they came would date a transfer that finished during an epoch
    by that epoch's end: the epoch's losses came first.)  It asks every
    ``PROBE_IDLE_POLL_S`` until a span has run ``PROBE_NEAR`` of the shorter
    of the last two of its name, then every ``PROBE_POLL_S`` (a first span,
    and one expected to last under ``PROBE_LONG_S``, from its start): a
    training loop's spans repeat, so most of a long epoch costs fifty
    wake-ups a second and not a thousand.  A span that ends early is dated at
    most one idle poll late, and the next of its name expects no more."""

    def __init__(self, tracer):
        self._tracer = tracer
        self._queue = queue.Queue()
        self._lock = threading.Lock()
        self._thread = None
        self._took = {}  # name -> seconds its last two spans took
        self.lost = 0

    def submit(self, arrays, name, t0, phase, parent, attrs) -> bool:
        with self._lock:
            if self._queue.unfinished_tasks >= PROBE_DEPTH:
                self.lost += 1
                return False
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="dk-telemetry-probe", daemon=True)
                self._thread.start()
        self._queue.put((arrays, name, t0, phase, parent, attrs))
        return True

    def _run(self):
        held = []
        while True:
            try:
                self._sweep(held)
            except Exception:  # noqa: BLE001 — this thread must not die of
                # a span it could not record (already let go of): count it
                with self._lock:
                    self.lost += 1

    def _due(self, item):
        """When to begin to look closely at ``item``: at once for the first
        of its name and for a short one."""
        _, name, t0 = item[:3]
        expect = min(self._took.get(name, (0.0,)))
        return t0 + PROBE_NEAR * expect if expect >= PROBE_LONG_S else t0

    def _sweep(self, held):
        """Wait for a new probe or for the next look (:func:`_poll_wait`),
        then settle every held probe whose arrays are ready."""
        wait = _poll_wait([self._due(item) for item in held],
                          self._tracer._clock())
        try:
            held.append(self._queue.get(timeout=wait))
            while True:
                held.append(self._queue.get_nowait())
        except queue.Empty:
            pass
        now = self._tracer._clock()
        for item in list(held):
            arrays, name, t0, phase, parent, attrs = item
            try:
                ready = _ready(arrays)
            except Exception:  # noqa: BLE001 — a deleted or failed array:
                # its error surfaces on the thread that reads the result
                ready = None
            if ready is False:
                continue
            # settled: let go of it before recording, so that a span that
            # cannot be recorded is not looked at again (by identity:
            # ``list.remove`` would compare the arrays of the probes before
            # it, and a jax array raises on ``==`` with a tuple)
            held[:] = [other for other in held if other is not item]
            self._queue.task_done()
            if ready:
                self._took[name] = (self._took.get(name, (now - t0,))[-1],
                                    now - t0)
                self._tracer._record(name, t0, now, parent, attrs, phase)
            else:
                with self._lock:
                    self.lost += 1

    def drain(self, timeout) -> bool:
        """Wait, at most ``timeout`` seconds, until every submitted probe
        has been recorded.  True if none is left."""
        deadline = time.monotonic() + timeout
        done = self._queue.all_tasks_done
        with done:
            while self._queue.unfinished_tasks:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                done.wait(left)
        return True


class Tracer:
    """Thread-safe span recorder with Chrome trace-event export.

    ``clock`` and ``pid`` are injectable so golden-file tests are
    deterministic; production code uses the module-global :data:`trace`.

    Only a ``correlated`` tracer stamps the fleet ``run_id`` into event args
    and feeds finished spans to the flight-recorder ring — the module-global
    :data:`trace` is; ad-hoc tracers (golden tests, scripts) default to
    uncorrelated so their output is a pure function of their inputs.

    ``anchor`` is one reading of two clocks taken together at the tracer's
    origin, ``(clock(), time.time_ns())``: the events' ``ts`` count from the
    first, so the second puts a written trace on the wall clock, beside logs
    and other processes' files.  :meth:`write` stores it in the file.
    """

    def __init__(self, clock=time.perf_counter, pid=None, correlated=False):
        self._clock = clock
        self._pid = pid
        self._correlated = correlated
        self._lock = threading.Lock()
        self._events = []
        self._tls = threading.local()
        self._tids = {}
        self._origin = clock()
        self.anchor = (self._origin, time.time_ns())
        self._probe = _ReadinessProbe(self)
        # the interpreter's own pauses (watch_gc): seconds and count of every
        # collection since the hook went in; the long ones wait here for the
        # next span to take them into the ring
        self.gc_seconds = 0.0
        self.gc_collections = 0
        self._gc_watched = False
        self._gc_t0 = None
        self._gc_pending = collections.deque()

    # ------------------------------------------------------------- recording

    def span(self, name, phase=None, **attrs):
        if not runtime.enabled():
            return NOOP_SPAN
        return Span(self, name, phase, attrs)

    def loop_span(self, name, phase=None, observe=None, **attrs):
        """A span of a loop's own iteration (the training loop's epoch, the
        serving loop's): recorded into the flight-recorder ring whether or
        not telemetry is on (module docstring), and stamped with the
        ``epoch`` or ``iter`` of the span of that grain that encloses it.
        A handful an iteration, never one per window or per request;
        attributes small and fixed (no request's ids).  ``observe(seconds)``
        is called when the span is recorded."""
        return _LoopSpan(self, name, phase, attrs, observe)

    def probe(self, arrays, name, t0, phase=None, **attrs):
        """Record the span ``t0 -> arrays ready on the device`` without
        waiting here: the readiness thread watches them.  Loop-grain like
        :meth:`loop_span` (always recorded; the iteration's index and
        ``parent`` are this thread's current ones).  Never pass a buffer
        that a later dispatch donates.  False if the probe was dropped (queue
        full) and counted in :attr:`probes_lost`."""
        iteration = getattr(self._tls, "iteration", None)
        if iteration is not None:
            attrs.setdefault(*iteration)
        return self._probe.submit(arrays, name, t0, phase, self.current(),
                                  attrs)

    def watch_gc(self) -> None:
        """Time the interpreter's garbage collections from now on (one
        ``gc.callbacks`` hook a tracer, however often this is called; the
        first ``ServingEngine.start()`` or trainer fit of the process calls
        it on the module's :data:`trace`, switch or no switch).  A collection
        holds the interpreter, so it stalls every thread whoever triggered
        it: :attr:`gc_seconds` and :attr:`gc_collections` count them all, and
        one of over ``GC_SPAN_S`` leaves a ``gc`` span (``generation``,
        ``collected``)."""
        with self._lock:
            if self._gc_watched:
                return
            self._gc_watched = True
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        # the collector runs this on the thread that tipped it over, with the
        # interpreter held and possibly inside a lock of the ring's or of
        # this tracer's: it touches no lock and records nothing itself
        if phase == "start":
            self._gc_t0 = self._clock()
            return
        t0, self._gc_t0 = self._gc_t0, None
        if t0 is None:
            return  # the hook went in during this collection
        t1 = self._clock()
        self.gc_seconds += t1 - t0
        self.gc_collections += 1
        if t1 - t0 > GC_SPAN_S:
            self._gc_pending.append((
                t0, t1, self.current(),
                {"generation": info.get("generation"),
                 "collected": info.get("collected")},
                (threading.current_thread().name, threading.get_ident())))

    def drain(self, timeout=1.0) -> bool:
        """Wait, at most ``timeout`` seconds, for the readiness thread to
        record the probes it holds (their arrays are ready by the time a fit
        has read its last losses, so this returns at once there)."""
        return self._probe.drain(timeout)

    @property
    def probes_lost(self) -> int:
        """Probes whose span was never recorded: dropped at a full queue,
        or their arrays raised.  ``/healthz`` shows it."""
        return self._probe.lost

    def _stack(self):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _push(self, name):
        self._stack().append(name)

    def _pop(self):
        stack = self._stack()
        stack.pop()
        return stack[-1] if stack else None

    def current(self):
        """Name of this thread's innermost open span, or ``None`` — used by
        the sanitizer to attribute violations to the pipeline phase."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    # ------------------------------------------------------- trace context

    def bind(self, trace_id=None, request_id=None, **extra):
        """Bind a trace context to the current thread for the duration of a
        ``with`` block.  Every span recorded by this thread while bound
        carries the bound ids in its event args (explicit span attrs win
        over the context).  Falsy values are skipped, so
        ``bind(trace_id=req.trace_id)`` is safe when the id may be empty.

        Works whether or not telemetry is enabled — binding is a couple of
        thread-local writes; it is the spans that no-op when disabled."""
        ctx = dict(getattr(self._tls, "ctx", None) or {})
        if trace_id:
            ctx["trace_id"] = trace_id
        if request_id:
            ctx["request_id"] = request_id
        for key, value in extra.items():
            if value:
                ctx[key] = value
        return _ContextBinding(self, ctx)

    def context(self) -> dict:
        """A copy of the current thread's bound trace context (``{}`` when
        unbound) — e.g. ``trace.context().get("trace_id")``."""
        return dict(getattr(self._tls, "ctx", None) or {})

    def record(self, name, t0, t1, **attrs):
        """Record an already-timed span (``perf_counter`` endpoints) without
        entering a context manager — for threads attributing work that began
        elsewhere, like the engine loop recording a request's queue wait
        from its admission-thread enqueue timestamp."""
        if not runtime.enabled():
            return
        self._record(name, t0, t1, None, attrs)

    def _record_loop(self, name, t0, t1, parent, attrs, phase):
        """A loop-grain span, behind the collections' spans that ``_on_gc``
        left for it (only this grain takes them in: where no loop runs,
        nothing always-on is recorded)."""
        while self._gc_pending:
            try:
                pause = self._gc_pending.popleft()
            except IndexError:
                break  # another thread took it
            self._record("gc", *pause[:4], origin=pause[4])
        self._record(name, t0, t1, parent, attrs, phase)

    def _record(self, name, t0, t1, parent, attrs, phase=None, origin=None):
        """``origin`` is the ``(name, ident)`` of the thread the span ran
        on, where that is not the one recording it."""
        thread, ident = origin or (threading.current_thread().name,
                                   threading.get_ident())
        if not runtime.enabled():
            # a loop-grain span with telemetry off: the ring alone
            if self._correlated:
                _flight_recorder.record_timed_span(
                    name, t0, t1, thread, parent, attrs)
            return
        if phase is not None:
            _registry.histogram(
                f"phase_{phase}_seconds",
                help=f"seconds in the {phase} phase",
            ).observe(t1 - t0)
        args = dict(attrs)
        ctx = getattr(self._tls, "ctx", None)
        if ctx:
            for key, value in ctx.items():
                args.setdefault(key, value)
        if parent is not None:
            # a parent that the span names itself (a request's span on the
            # serving loop's thread names the span of the thread that
            # admitted it) is not the enclosing span's to overwrite
            args.setdefault("parent", parent)
        if self._correlated:
            rid = _correlate.current()
            if rid is not None:
                args["run_id"] = rid
        with self._lock:
            tid = self._tids.setdefault(ident, len(self._tids))
            event = {
                "name": name,
                "cat": "distkeras",
                "ph": "X",
                "pid": self._pid if self._pid is not None else os.getpid(),
                "tid": tid,
                "ts": round((t0 - self._origin) * 1e6, 3),
                "dur": round((t1 - t0) * 1e6, 3),
                "args": args,
            }
            self._events.append(event)
        if self._correlated:
            _flight_recorder.record_timed_span(
                name, t0, t1, thread, parent, attrs, event)

    def reset(self):
        self._probe.drain(1.0)
        self._gc_pending.clear()
        with self._lock:
            self._events.clear()
            self._tids.clear()
            self._origin = self._clock()
            self.anchor = (self._origin, time.time_ns())

    # --------------------------------------------------------------- export

    def events(self):
        with self._lock:
            return [dict(e) for e in self._events]

    def export(self) -> dict:
        """Chrome trace-event JSON object; open in Perfetto / chrome://tracing."""
        evs = self.events()
        evs.sort(key=lambda e: (e["tid"], e["ts"], -e["dur"]))
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def write(self, path) -> str:
        payload = self.export()
        perf, wall_ns = self.anchor
        # ``ts`` counts microseconds from ``perf_counter_s``; the wall clock
        # read ``time_ns`` at that moment
        payload["otherData"] = {"clock_anchor": {
            "perf_counter_s": perf, "time_ns": wall_ns}}
        # tmp + replace: dktrace merge / flightdeck may read this file from
        # another process while a dump is still streaming out
        tmp = os.fspath(path) + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(payload, fh, indent=1)
        os.replace(tmp, path)
        return path


# Process-global tracer used by all instrumentation sites; correlated so its
# events carry the fleet run_id and land in the flight-recorder ring.
trace = Tracer(correlated=True)
