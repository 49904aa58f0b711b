"""The serving loop records its own iteration, always: with
``DISTKERAS_TELEMETRY`` unset every pass of the host loop leaves its spans in
the flight-recorder ring (``serving.loop`` and, beneath it, ``.admit``,
``.prefill``, ``.dispatch``, ``.wait``, ``.emit``; ``serving.loop.idle`` for a
pass that found nothing to do), each timed span also one observation of a
``serving_loop_*`` histogram from the same two clock reads; every dispatched
program has its place in dispatch order (``seq``) and is counted, with those
that found the device empty; the interpreter's collections are timed by one
process-wide hook.  On the CPU a step is shorter than the host's part, so
where the device must be held the tests use the gate of
``tests/test_serving_dispatch_ahead.py``."""

import gc
import threading
import time

import jax
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import TransformerLM
from distkeras_tpu.serving import GenerateRequest, ServingEngine
from distkeras_tpu.serving.engine import serving_metrics
from distkeras_tpu.telemetry.flightdeck import recorder
from distkeras_tpu.telemetry.metrics import Registry
from distkeras_tpu.telemetry.trace import Tracer

from test_serving_dispatch_ahead import Gate, _counter, _idle

VOCAB = 23
PHASES = ("admit", "prefill", "dispatch", "wait", "emit")
TIMED = {"serving.loop": "serving_loop_iteration_seconds",
         "serving.loop.dispatch": "serving_loop_dispatch_seconds",
         "serving.loop.wait": "serving_loop_wait_seconds",
         "serving.loop.emit": "serving_loop_emit_seconds",
         "serving.loop.idle": "serving_loop_idle_seconds"}


@pytest.fixture(scope="module")
def lm():
    module = TransformerLM(vocab_size=VOCAB, dim=16, heads=2, num_layers=2,
                           max_len=48)
    init = lambda seed, m=module: m.init(
        jax.random.PRNGKey(seed), np.zeros((1, 4), np.int32))["params"]
    draft = TransformerLM(vocab_size=VOCAB, dim=16, heads=2, num_layers=1,
                          max_len=48)
    return module, init(0), draft, init(1, draft)


@pytest.fixture
def ring():
    """Telemetry off, and the process's ring emptied: it holds 2048 entries,
    many times what a run of these tests writes."""
    telemetry.configure(False)
    recorder.reset()
    yield recorder
    telemetry.configure(None)


@pytest.fixture
def make_engine(lm, ring):
    engines = []

    def factory(**kw):
        kw.setdefault("registry", Registry())
        kw.setdefault("num_slots", 3)
        kw.setdefault("page_size", 8)
        engine = ServingEngine(lm[0], lm[1], **kw)
        engines.append(engine)
        return engine

    yield factory
    for engine in engines:
        engine.stop()


def _loop_spans(ring):
    return [s for s in ring.spans() if s["name"].startswith("serving.loop")]


def _known_run(engine):
    """Five requests of different lengths through three slots, then the
    loop left to go idle."""
    shapes = [(3, 6), (7, 4), (5, 9), (3, 1), (6, 5)]
    rng = np.random.default_rng(2)
    pendings = [engine.submit(GenerateRequest(
        prompt=rng.integers(0, VOCAB, size=n).tolist(), max_new_tokens=new))
        for n, new in shapes]
    for pending, (_, new) in zip(pendings, shapes):
        assert len(pending.result(timeout=120).tokens) == new
    _idle(engine)
    time.sleep(0.12)  # two sleeps of the idle loop
    return shapes


# ------------------------------------------------------------- the spans


def test_a_run_with_telemetry_off_leaves_the_loops_spans_in_the_ring(
        make_engine, ring):
    engine = make_engine()
    _known_run(engine)
    engine.stop()
    names = {s["name"] for s in ring.spans()}
    assert {"serving.loop", "serving.loop.idle"} | {
        "serving.loop." + phase for phase in PHASES} <= names
    assert all(s["thread"] == "serving-engine" for s in _loop_spans(ring))
    # the switch is off: nothing in the tracer's own list, no request span
    assert telemetry.trace.export()["traceEvents"] == []
    assert not names & {"serving.prefill", "serving.decode_step",
                        "serving.queue_wait", "serving.admit"}
    # attributes stay small and fixed: no request's ids on this grain
    kinds = {type(v) for s in _loop_spans(ring) for v in s["attrs"].values()}
    assert kinds <= {int, bool, str}
    assert not any(key in s["attrs"] for s in _loop_spans(ring)
                   for key in ("request_id", "trace_id", "tenant"))


def test_children_carry_their_iterations_iter_and_lie_inside_it(
        make_engine, ring):
    engine = make_engine()
    _known_run(engine)
    engine.stop()
    spans = _loop_spans(ring)
    passes = {s["attrs"]["iter"]: s for s in spans
              if s["name"] in ("serving.loop", "serving.loop.idle")}
    # one span a pass, numbered in order, and a pass is one or the other
    assert len(passes) == sum(
        s["name"] in ("serving.loop", "serving.loop.idle") for s in spans)
    ordered = sorted(passes.values(), key=lambda s: s["t0"])
    assert [s["attrs"]["iter"] for s in ordered] == sorted(passes)
    children = [s for s in spans if s["name"] not in
                ("serving.loop", "serving.loop.idle")]
    assert children
    for child in children:
        outer = passes[child["attrs"]["iter"]]
        assert outer["t0"] <= child["t0"] and child["t1"] <= outer["t1"]
        if child["name"] == "serving.loop.prefill":
            assert child["parent"] == "serving.loop.admit"
        else:
            assert child["parent"] == outer["name"]
    # a pass that admitted or stepped says what it did; the loop thread is
    # never under two spans of one grain at once
    for s in ordered:
        if s["name"] == "serving.loop":
            assert s["attrs"]["admitted"] or s["attrs"]["active"]
            assert s["attrs"]["starved"] >= 0
    for before, after in zip(ordered, ordered[1:]):
        assert before["t1"] <= after["t0"]


def test_seq_counts_every_dispatched_program_once(make_engine, ring):
    registry = Registry()
    engine = make_engine(registry=registry)
    shapes = _known_run(engine)
    engine.stop()
    spans = _loop_spans(ring)
    dispatched = sorted(
        s["attrs"]["seq"] for s in spans
        if s["name"] in ("serving.loop.prefill", "serving.loop.dispatch"))
    # one prefill a request, and a step serves every active slot at once
    steps = int(_counter(registry, "serving_decode_steps_total"))
    assert max(new for _, new in shapes) - 1 <= steps < sum(
        new - 1 for _, new in shapes)
    programs = len(shapes) + steps
    assert dispatched == list(range(programs))
    assert _counter(registry, "serving_dispatches_total") == programs
    for name in ("serving.loop.wait", "serving.loop.emit"):
        assert sorted(s["attrs"]["seq"] for s in spans
                      if s["name"] == name) == list(range(programs))
    waits = {s["attrs"]["seq"]: s for s in spans
             if s["name"] == "serving.loop.wait"}
    prefills = {s["attrs"]["seq"] for s in spans
                if s["name"] == "serving.loop.prefill"}
    assert {seq for seq, s in waits.items()
            if s["attrs"]["kind"] == "prefill"} == prefills
    # a program is read after it was dispatched, and programs are read in
    # dispatch order
    sent = {s["attrs"]["seq"]: s for s in spans
            if s["name"] in ("serving.loop.prefill", "serving.loop.dispatch")}
    assert all(sent[seq]["t0"] <= waits[seq]["t0"] for seq in waits)
    reads = sorted(waits.values(), key=lambda s: s["t0"])
    assert [s["attrs"]["seq"] for s in reads] == list(range(programs))
    emits = [s for s in spans if s["name"] == "serving.loop.emit"]
    assert sum(s["attrs"]["finished"] for s in emits) == len(shapes)
    assert sum(s["attrs"]["rows"] for s in emits) == sum(
        new for _, new in shapes)
    prefill = next(s for s in spans if s["name"] == "serving.loop.prefill")
    assert {"slot", "width", "plen", "seq", "iter"} == set(prefill["attrs"])
    step = next(s for s in spans if s["name"] == "serving.loop.dispatch")
    assert {"seq", "active", "uploaded", "level", "iter"} == set(step["attrs"])
    assert step["attrs"]["uploaded"] is True  # the first step after an admit


def test_one_observation_a_span(make_engine, ring):
    registry = Registry()
    engine = make_engine(registry=registry)
    shapes = _known_run(engine)
    engine.stop()
    spans = _loop_spans(ring)
    snap = registry.snapshot()
    for name, instrument in sorted(TIMED.items()):
        mine = [s for s in spans if s["name"] == name]
        assert snap[instrument]["count"] == len(mine) > 0, name
        assert snap[instrument]["sum"] == pytest.approx(
            sum(s["t1"] - s["t0"] for s in mine), rel=1e-9), name
    # the two older histograms keep their one observation a step, a prefill
    steps = snap["serving_decode_steps_total"]["value"]
    assert snap["serving_token_latency_seconds"]["count"] == steps
    assert snap["serving_loop_dispatch_seconds"]["count"] == steps
    assert snap["serving_prefill_seconds"]["count"] == len(shapes)
    # and every admitted request's time in the queue is observed, always
    assert snap["serving_queue_wait_seconds"]["count"] == len(shapes)
    assert snap["serving_queue_wait_seconds"]["sum"] > 0
    # a step's call is its dispatch and the waits and emits inside it
    assert snap["serving_token_latency_seconds"]["sum"] >= snap[
        "serving_loop_dispatch_seconds"]["sum"]


def test_an_idle_engine_writes_its_sleeps_and_nothing_else(make_engine, ring):
    engine = make_engine()
    engine.generate([1, 2, 3], max_new_tokens=2, timeout=120)
    _idle(engine)
    time.sleep(0.1)
    ring.reset()
    time.sleep(0.5)
    spans = [s for s in ring.spans() if s["name"] != "gc"]
    assert {s["name"] for s in spans} == {"serving.loop.idle"}
    assert 5 <= len(spans) <= 12  # a sleep is 50 ms: 20 spans a second
    assert all(set(s["attrs"]) == {"iter"} for s in spans)


def test_the_nine_instruments_stand_on_the_engines_registry(make_engine):
    registry = Registry()
    make_engine(registry=registry)
    assert {"serving_loop_iteration_seconds", "serving_loop_dispatch_seconds",
            "serving_loop_wait_seconds", "serving_loop_emit_seconds",
            "serving_loop_idle_seconds", "serving_queue_wait_seconds",
            "serving_dispatches_total", "serving_dispatches_starved_total",
            "serving_gc_pause_seconds_total"} <= set(registry.snapshot())


# ------------------------------------------------------ starved dispatches


def _hold_the_read_behind_step(engine, registry, n):
    """A gate at the loop's read of what lies behind the n-th decode step:
    that step is dispatched and unread."""
    dispatched = lambda engine, keep, t0: keep == 1 and _counter(
        registry, "serving_decode_steps_total") >= n
    return Gate(engine, "_read_behind", dispatched)


def test_a_program_that_finished_before_the_next_dispatch_starved_it(
        make_engine, ring):
    """The counter's rule, with the device's answer to ``is_ready()`` in the
    test's hands: a device that is always behind the host starves no
    dispatch, one that is always ahead starves every dispatch that has an
    unread program before it."""
    registry = Registry()
    engine = make_engine(registry=registry)
    ready = {"answer": True}

    class Tok:
        """A program's tokens whose readiness the test decides."""

        def __init__(self, real):
            self.real = real

        def is_ready(self):
            return ready["answer"]

        def __array__(self, *args, **kwargs):
            return np.asarray(self.real)

    # every in-flight record's tokens answer ``is_ready`` as the test says
    # (the chain on the device keeps the real arrays)
    class Records(type(engine._inflight)):
        def append(self, rec):
            rec.tok = Tok(rec.tok)
            super().append(rec)

    engine._inflight = Records()
    starved = lambda: _counter(registry, "serving_dispatches_starved_total")

    # device always behind the host: no dispatch is starved, but the first
    # of an answer that follows a flush with no sleep between
    ready["answer"] = False
    engine.generate([1, 2, 3], max_new_tokens=6, timeout=120)
    assert starved() == 0
    # device always ahead of the host: every dispatch behind an unread
    # program is starved (5 steps; the prefill followed an idle sleep)
    _idle(engine)
    time.sleep(0.12)
    ready["answer"] = True
    engine.generate([1, 2, 3], max_new_tokens=6, timeout=120)
    assert starved() == 5
    assert _counter(registry, "serving_dispatches_total") == 12
    engine.stop()
    per_pass = [s["attrs"]["starved"] for s in ring.spans()
                if s["name"] == "serving.loop"]
    assert sum(per_pass) == 5 and max(per_pass) == 1


def test_a_gated_step_that_finishes_counts_one_starved_dispatch(
        make_engine, ring):
    registry = Registry()
    engine = make_engine(registry=registry)
    engine.generate([4, 4, 2], max_new_tokens=2, timeout=120)  # compiled
    _idle(engine)
    time.sleep(0.12)
    gate = _hold_the_read_behind_step(
        engine, registry, _counter(registry, "serving_decode_steps_total") + 3)
    pending = engine.submit(GenerateRequest(prompt=[1, 2, 3],
                                            max_new_tokens=12))
    assert gate.reached.wait(60)
    before = _counter(registry, "serving_dispatches_starved_total")
    newest = engine._inflight[-1]
    jax.block_until_ready(newest.tok)  # the held step finishes on the device
    gate.open()
    assert len(pending.result(timeout=120).tokens) == 12
    engine.stop()
    # the dispatch right behind the held step found it finished
    held_next = next(
        s for s in ring.spans() if s["name"] == "serving.loop.dispatch"
        and s["attrs"]["seq"] == newest.seq + 1)
    outer = next(s for s in ring.spans() if s["name"] == "serving.loop"
                 and s["attrs"]["iter"] == held_next["attrs"]["iter"])
    assert outer["attrs"]["starved"] == 1
    assert _counter(registry, "serving_dispatches_starved_total") > before


def test_a_dispatch_after_an_idle_sleep_is_not_starved(make_engine, ring):
    registry = Registry()
    engine = make_engine(registry=registry)
    for _ in range(3):
        # an answer of one token: a prefill and no step, each after a sleep
        assert len(engine.generate([5, 6], max_new_tokens=1,
                                   timeout=120).tokens) == 1
        _idle(engine)
        time.sleep(0.12)
    assert _counter(registry, "serving_dispatches_total") == 3
    assert _counter(registry, "serving_dispatches_starved_total") == 0


def test_the_serial_speculative_loop_counts_every_dispatch_starved(lm, ring):
    module, params, draft, draft_params = lm
    registry = Registry()
    engine = ServingEngine(module, params, num_slots=2, page_size=8,
                           registry=registry, draft_model=draft,
                           draft_params=draft_params, spec_tokens=3)
    try:
        result = engine.generate([3, 1, 4, 1], max_new_tokens=9, timeout=240)
        assert len(result.tokens) == 9
    finally:
        engine.stop()
    programs = _counter(registry, "serving_dispatches_total")
    iterations = _counter(registry, "serving_decode_steps_total")
    # a target and a draft prefill, then 3 draft steps and a verify a window
    assert programs == 2 + 4 * iterations
    # all but the first, which followed the loop's sleep
    assert _counter(registry, "serving_dispatches_starved_total") == programs - 1
    spans = _loop_spans(ring)
    steps = [s for s in spans if s["name"] == "serving.loop.dispatch"]
    assert len(steps) == iterations
    # the same three phases around the serial steps, paired by the verify's seq
    for name in ("serving.loop.wait", "serving.loop.emit"):
        assert {s["attrs"]["seq"] for s in steps} <= {
            s["attrs"]["seq"] for s in spans if s["name"] == name}
    assert all(s["attrs"]["level"] == 2 and s["attrs"]["uploaded"]
               for s in steps)
    snap = registry.snapshot()
    assert snap["serving_loop_dispatch_seconds"]["count"] == iterations
    assert snap["serving_token_latency_seconds"]["count"] == iterations


# ------------------------------------------------- the interpreter's pauses


def test_watch_gc_installs_one_hook_however_often(ring):
    before = list(gc.callbacks)
    telemetry.trace.watch_gc()
    once = list(gc.callbacks)
    telemetry.trace.watch_gc()
    assert gc.callbacks == once
    assert len(once) - len(before) in (0, 1)  # 0: an earlier test's engine
    assert sum(cb == telemetry.trace._on_gc for cb in gc.callbacks) == 1


def test_a_collection_inside_a_run_moves_the_pause_counter(make_engine, ring):
    registry = Registry()
    engine = make_engine(registry=registry)
    engine.generate([1, 2, 3], max_new_tokens=2, timeout=120)
    step_tokens = engine._step_tokens

    def collecting(rec, toks, dt):
        gc.collect()  # on the loop thread, inside a serving.loop
        return step_tokens(rec, toks, dt)

    seconds, count = telemetry.trace.gc_seconds, telemetry.trace.gc_collections
    before = _counter(registry, "serving_gc_pause_seconds_total")
    engine._step_tokens = collecting
    engine.generate([1, 2, 3], max_new_tokens=5, timeout=120)
    engine.stop()
    gained = _counter(registry, "serving_gc_pause_seconds_total") - before
    assert gained > 0
    assert telemetry.trace.gc_collections >= count + 4
    # the counter holds what the process-wide total gained inside the loop's
    # iterations: never more than it gained in all
    assert gained <= telemetry.trace.gc_seconds - seconds + 1e-9


def test_a_slow_collection_leaves_a_gc_span(ring):
    """The hook on a tracer with a clock of the test's own: a collection of
    3 ms is a span in the ring with the next span that enters it, under the
    span that was open on its thread; one of 0.1 ms only counts."""
    now = [100.0]
    tracer = Tracer(clock=lambda: now[0], correlated=True)
    with tracer.loop_span("serving.loop", iter=7):
        tracer._on_gc("start", {"generation": 2})
        now[0] += 0.003
        tracer._on_gc("stop", {"generation": 2, "collected": 41,
                               "uncollectable": 0})
        tracer._on_gc("start", {"generation": 0})
        now[0] += 0.0001
        tracer._on_gc("stop", {"generation": 0, "collected": 1})
        assert ring.spans() == []  # the hook itself touches no ring
    assert tracer.gc_collections == 2
    assert tracer.gc_seconds == pytest.approx(0.0031)
    pause, loop = ring.spans()
    assert loop["name"] == "serving.loop"
    assert pause["name"] == "gc" and pause["parent"] == "serving.loop"
    assert pause["attrs"] == {"generation": 2, "collected": 41}
    assert (pause["t0"], pause["t1"]) == pytest.approx((100.0, 100.003))
    assert pause["thread"] == threading.current_thread().name
    # a stop without its start (the hook went in mid-collection) is nothing
    tracer._on_gc("stop", {"generation": 1, "collected": 0})
    assert tracer.gc_collections == 2


def test_a_real_slow_collection_is_seen_by_the_installed_hook(ring):
    telemetry.trace.watch_gc()
    count = telemetry.trace.gc_collections

    class Knot:
        def __init__(self):
            self.me = self

    knots = [Knot() for _ in range(200_000)]
    del knots
    gc.collect()
    assert telemetry.trace.gc_collections > count
    with telemetry.trace.loop_span("after"):
        pass
    pauses = [s for s in ring.spans() if s["name"] == "gc"]
    assert pauses == sorted(pauses, key=lambda s: s["t0"])  # in their order
    assert pauses[-1]["attrs"]["generation"] == 2
    assert pauses[-1]["attrs"]["collected"] >= 200_000
    assert pauses[-1]["t1"] - pauses[-1]["t0"] > 1e-3


# ----------------------------------------------------- one grain, one class


def test_the_training_loops_epoch_and_the_serving_loops_iter_share_a_class(
        ring):
    tracer = Tracer(correlated=True)
    with tracer.loop_span("epoch", epoch=3, epochs=1):
        with tracer.loop_span("dispatch", windows=2):
            pass
    with tracer.loop_span("serving.loop", iter=9) as outer:
        with tracer.loop_span("serving.loop.wait", seq=1):
            pass
        with tracer.loop_span("dropped") as inner:
            inner.keep = False
        outer.attrs["admitted"] = 0
    seen = []
    with tracer.loop_span("timed", observe=seen.append):
        pass
    rows = {s["name"]: s for s in ring.spans()}
    assert "dropped" not in rows
    assert rows["dispatch"]["attrs"] == {"windows": 2, "epoch": 3}
    assert rows["serving.loop.wait"]["attrs"] == {"seq": 1, "iter": 9}
    assert rows["serving.loop"]["attrs"] == {"iter": 9, "admitted": 0}
    assert "iter" not in rows["timed"]["attrs"]  # the iteration was over
    assert seen == [rows["timed"]["t1"] - rows["timed"]["t0"]]
    assert type(tracer.loop_span("a")) is type(tracer.loop_span("b", iter=1))


def test_a_request_span_keeps_the_parent_it_names(ring, monkeypatch, tmp_path):
    """With the switch on, the loop's spans enclose the request-grain ones
    on the loop thread; a request's span still names the span of the thread
    that admitted it."""
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    telemetry.configure(True)
    telemetry.trace.reset()
    try:
        with telemetry.trace.loop_span("serving.loop", iter=0):
            with telemetry.trace.span("serving.prefill",
                                      parent="serving.admit", slot=0):
                pass
            with telemetry.trace.span("serving.decode_step", n_active=2):
                pass
        events = {e["name"]: e for e in telemetry.trace.export()["traceEvents"]}
        assert events["serving.prefill"]["args"]["parent"] == "serving.admit"
        assert events["serving.decode_step"]["args"]["parent"] == "serving.loop"
        assert events["serving.loop"]["args"]["iter"] == 0
    finally:
        telemetry.trace.reset()
        telemetry.configure(False)


# ------------------------------------------------------------- what it costs


def test_one_iterations_instrumentation_cost_pin():
    """One iteration's instrumentation alone: the helper's seven phases
    around empty bodies, ring and histograms.  It must stay within a constant
    factor of a plain dict store (some 30 us an iteration against 0.03: a
    ratio, with an absolute floor to stay unflaky on a loaded machine)."""
    telemetry.configure(False)
    metrics = serving_metrics(Registry())
    trace = telemetry.trace

    def phase(name, timed=None, **attrs):
        return trace.loop_span(
            name, observe=metrics[timed].observe if timed else None, **attrs)

    def iteration(i):
        with phase("serving.loop", "loop_iteration", iter=i) as outer:
            with phase("serving.loop.admit") as span:
                span.attrs["admitted"] = 0
            with phase("serving.loop.dispatch", "loop_dispatch",
                       active=3) as span:
                span.attrs.update(seq=2 * i, uploaded=False, level=0)
            for kind in ("step", "prefill"):
                with phase("serving.loop.wait", "loop_wait", seq=i, kind=kind):
                    pass
                with phase("serving.loop.emit", "loop_emit", seq=i,
                           rows=3) as span:
                    span.attrs["finished"] = 0
            outer.attrs.update(admitted=0, active=3, starved=0)

    try:
        n = 3000
        for i in range(300):
            iteration(i)
        d = {}
        t0 = time.perf_counter()
        for i in range(n):
            d["k"] = i
        dict_t = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(n):
            iteration(i)
        loop_t = time.perf_counter() - t0
    finally:
        telemetry.configure(None)
    print(f"one iteration's instrumentation: {1e6 * loop_t / n:.1f} us "
          f"(a dict store {1e6 * dict_t / n:.3f} us)")
    assert loop_t < max(5000 * dict_t, n * 400e-6), (
        f"{1e6 * loop_t / n:.1f} us an iteration against "
        f"{1e6 * dict_t / n:.3f} us a dict store")
