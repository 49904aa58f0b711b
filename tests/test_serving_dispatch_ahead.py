"""The serving loop dispatches ahead: what only the device knows (the token
just sampled, the keys) is chained on the device, and the host reads every
program's tokens while the next one runs.  On the CPU a step is shorter than
the host's part, so nothing here depends on the overlap being real: the tests
pin the *order* (dispatch N+1 before read N), what a steady step uploads
(nothing), the answers (token for token the serial loop's), what happens to
the token of the step that an EOS rides too long (dropped, uncounted,
unbilled), and that ``cancel`` / ``drain`` / ``hot_swap`` / ``stop`` hand back
what the device made when they meet a step in flight."""

import sys
import threading
import time

import jax
import numpy as np
import pytest

from distkeras_tpu import chaos, telemetry
from distkeras_tpu.models import TransformerLM
from distkeras_tpu.models.generate import greedy_generate_module
from distkeras_tpu.serving import GenerateRequest, ServingEngine
from distkeras_tpu.telemetry import accounting
from distkeras_tpu.telemetry.metrics import Registry

VOCAB = 23


@pytest.fixture(scope="module")
def lm():
    module = TransformerLM(vocab_size=VOCAB, dim=16, heads=2, num_layers=2,
                           max_len=48)
    init = lambda seed: module.init(
        jax.random.PRNGKey(seed), np.zeros((1, 4), np.int32))["params"]
    return module, init(0), init(1)


@pytest.fixture
def make_engine(lm):
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


def _ref(module, params, prompt, steps):
    out = greedy_generate_module(
        module, params, np.asarray([prompt], np.int32), steps)
    return out[0, len(prompt):].tolist()


def _counter(registry, name):
    entry = registry.snapshot().get(name)
    return 0.0 if entry is None else float(entry["value"])


def _idle(engine):
    """Wait until the loop has read everything it dispatched."""
    deadline = time.monotonic() + 10
    while engine._inflight and time.monotonic() < deadline:
        time.sleep(0.005)


def make_serial(engine):
    """The same engine with the host reading every program as soon as it has
    dispatched it: the serial order, the reference for what chaining may not
    change."""
    behind = engine._read_behind
    engine._read_behind = lambda keep, t0: behind(0, t0)
    return engine


class Gate:
    """Holds the loop thread at one of the engine's calls: ``when(engine,
    *args)`` is asked before each call of ``name``; the first time it says
    yes the loop stops there (``reached``) until ``open()``."""

    def __init__(self, engine, name, when):
        self.reached, self._go = threading.Event(), threading.Event()
        real = getattr(engine, name)

        def held(*args):
            if not self.reached.is_set() and when(engine, *args):
                self.reached.set()
                assert self._go.wait(60)
            return real(*args)

        setattr(engine, name, held)

    def open(self):
        self._go.set()


@pytest.fixture
def gated(make_engine):
    """An engine, its registry, and a gate at the loop's read of what lies
    behind the n-th decode step: that step is dispatched and unread."""

    def factory(n, **kw):
        registry = Registry()
        engine = make_engine(registry=registry, **kw)
        dispatched = lambda engine, keep, t0: keep == 1 and _counter(
            registry, "serving_decode_steps_total") >= n
        return engine, registry, Gate(engine, "_read_behind", dispatched)

    return factory


# ------------------------------------------------------------ same answers


def test_staggered_greedy_admitted_and_retired_with_a_step_in_flight(
        lm, make_engine):
    """More requests than slots, of different lengths, arriving while steps
    are in flight: every answer is ``generate``'s, token for token, and the
    loop did chain steps while it served them."""
    module, params, _ = lm
    registry = Registry()
    engine = make_engine(registry=registry)
    rng = np.random.default_rng(11)
    shapes = [(3, 9), (7, 5), (5, 12), (3, 2), (7, 1), (5, 7), (3, 11)]
    prompts = [rng.integers(0, VOCAB, size=n).tolist() for n, _ in shapes]
    pendings = []
    for prompt, (_, new) in zip(prompts, shapes):
        pendings.append(engine.submit(
            GenerateRequest(prompt=prompt, max_new_tokens=new)))
        time.sleep(0.004)
    for pending, prompt, (_, new) in zip(pendings, prompts, shapes):
        result = pending.result(timeout=120)
        assert result is not None and result.finish_reason == "length"
        assert result.tokens == _ref(module, params, prompt, new)
        assert result.ttft_s > 0 and result.latency_s >= result.ttft_s
    assert _counter(registry, "serving_decode_steps_chained_total") > 0
    assert _counter(registry, "serving_tokens_total") == sum(
        new for _, new in shapes)
    stats = engine.stats()
    assert stats["active_slots"] == 0 and stats["pages_in_use"] == 0


SAMPLED = {
    "warm": dict(temperature=0.7, seed=5),
    "hot_top_k": dict(temperature=1.3, top_k=6, seed=2**31 + 9),
    "top_p": dict(temperature=0.9, top_p=0.8, seed=77),
    "all_knobs": dict(temperature=1.1, top_k=9, top_p=0.9, seed=123456789),
}


@pytest.mark.parametrize("case", sorted(SAMPLED))
def test_seeded_sampling_equals_the_serial_order(lm, make_engine, case):
    """At a temperature the keys decide the tokens, and the keys never come
    to the host: chained on the device they give what the serial loop gave,
    alone and beside other traffic."""
    knobs = dict(SAMPLED[case], max_new_tokens=14, timeout=120)
    prompt = [5, 9, 2, 11]
    serial = make_serial(make_engine()).generate(prompt, **knobs)
    engine = make_engine()
    alone = engine.generate(prompt, **knobs)
    rng = np.random.default_rng(3)
    noise = [engine.submit(GenerateRequest(
        prompt=rng.integers(0, VOCAB, size=6).tolist(), max_new_tokens=9,
        temperature=0.5, seed=i)) for i in range(2)]
    busy = engine.generate(prompt, **knobs)
    assert all(p.result(timeout=120) is not None for p in noise)
    assert len(serial.tokens) == 14
    assert alone.tokens == serial.tokens
    assert busy.tokens == serial.tokens


def test_serial_reading_never_chains(make_engine):
    """The control of the counter: the same engine read serially chains no
    step."""
    registry = Registry()
    engine = make_serial(make_engine(registry=registry))
    assert len(engine.generate([1, 2, 3], max_new_tokens=6,
                               timeout=120).tokens) == 6
    assert _counter(registry, "serving_decode_steps_total") == 5
    assert _counter(registry, "serving_decode_steps_chained_total") == 0


# ------------------------------------------------------- EOS, one step late


@pytest.mark.parametrize("where", ["mid_answer", "first_token"])
def test_eos_ends_the_answer_and_the_overrun_token_is_dropped(
        lm, make_engine, where, tmp_path, monkeypatch):
    """The host sees an EOS one step late: the slot rides one step more,
    and that step's token is neither delivered nor counted nor billed."""
    module, params, _ = lm
    monkeypatch.setenv("DISTKERAS_TELEMETRY_DIR", str(tmp_path))
    telemetry.configure(True)
    accounting.configure(True)
    try:
        registry = Registry()
        engine = make_engine(registry=registry)
        prompt = [2, 7, 1, 8, 4]
        ref = _ref(module, params, prompt, 12)
        eos = ref[0] if where == "first_token" else ref[4]
        k = ref.index(eos)
        result = engine.generate(prompt, max_new_tokens=12, eos_id=eos,
                                 tenant="acme", timeout=120)
        assert result.finish_reason == "eos"
        assert result.tokens == ref[:k + 1]
        # token k came from step k (the prefill made token 0); step k + 1
        # was dispatched before the host had read it
        assert _counter(registry, "serving_decode_steps_total") == k + 1
        assert _counter(registry, "serving_tokens_total") == k + 1
        billed = engine._ledger.snapshot()["tenants"]
        assert sum(row["decode_tokens"] for row in billed) == k + 1
        # the overrun row lay inside the slot's own pages, all given back
        stats = engine.stats()
        assert stats["active_slots"] == 0 and stats["pages_in_use"] == 0
        # and the engine goes on as if nothing had been: the next answer
        again = engine.generate(prompt, max_new_tokens=12, timeout=120)
        assert again.tokens == ref
    finally:
        accounting.configure(None)
        accounting.reset()
        telemetry.configure(None)
        telemetry.trace.reset()


def test_eos_at_the_last_token_is_an_eos(lm, make_engine):
    """Where the length ends the answer at the very token that is the EOS,
    the reason is still ``eos`` (the slot was already given back by count)."""
    module, params, _ = lm
    engine = make_engine()
    prompt = [2, 7, 1, 8, 4]
    ref = _ref(module, params, prompt, 12)
    k = next(i for i in range(2, 12) if ref[i] not in ref[:i])
    result = engine.generate(prompt, max_new_tokens=k + 1, eos_id=ref[k],
                             timeout=120)
    assert result.finish_reason == "eos" and result.tokens == ref[:k + 1]


# ------------------------------------------------------------- the order


def test_dispatch_comes_before_read_and_a_steady_step_uploads_nothing(
        lm, make_engine):
    """The order itself, with the step wrapped: step N + 1 is dispatched
    before step N's tokens are read, the token, position and key inputs of
    a step are the device outputs of the step before, and a steady step (no
    admit, no retire since the last) gets the very arrays the last one got:
    nothing is uploaded."""
    module, params, _ = lm
    registry = Registry()
    engine = make_engine(registry=registry)
    events, calls = [], []
    decode, step_tokens = engine._decode, engine._step_tokens

    def dispatching(params_, kp, vp, *inputs):
        out = decode(params_, kp, vp, *inputs)
        events.append(("dispatch", len(calls)))
        calls.append((inputs, out[2:]))
        return out

    def reading(rec, toks, dt):
        index, = [i for i, (_, outs) in enumerate(calls) if outs[0] is rec.tok]
        events.append(("read", index))
        return step_tokens(rec, toks, dt)

    engine._decode, engine._step_tokens = dispatching, reading
    prompt, new = [4, 1, 9], 10
    result = engine.generate(prompt, max_new_tokens=new, timeout=120)
    assert result.tokens == _ref(module, params, prompt, new)

    steps = new - 1
    assert [e for e in events if e[0] == "dispatch"] == [
        ("dispatch", n) for n in range(steps)]
    assert [e for e in events if e[0] == "read"] == [
        ("read", n) for n in range(steps)]
    for n in range(steps - 1):
        assert events.index(("dispatch", n + 1)) < events.index(("read", n))
    for n in range(1, steps):
        inputs, _ = calls[n]
        before, (tok, new_pos, new_keys) = calls[n - 1]
        tables, pos, last, keys, temp, top_k, top_p, active = inputs
        assert all(isinstance(x, jax.Array) for x in inputs)
        assert last is tok and keys is new_keys  # never through the host
        if 2 <= n < steps - 1:
            # steady: the admit lies two steps back, the end is not yet;
            # the very arrays of the step before, and its positions
            assert pos is new_pos
            assert all(inputs[i] is before[i] for i in (0, 4, 5, 6, 7))
    # every step but the first was dispatched with the one before it unread
    assert _counter(registry, "serving_decode_steps_total") == steps
    assert _counter(registry, "serving_decode_steps_chained_total") == steps - 1


def test_chained_counter_after_a_known_run(make_engine):
    """Two answers with the loop idle between them: each one's first step
    follows a flush (nothing to chain to), every other step is chained."""
    registry = Registry()
    engine = make_engine(registry=registry)
    for new in (7, 4):
        assert len(engine.generate([3, 1, 4], max_new_tokens=new,
                                   timeout=120).tokens) == new
        _idle(engine)
    assert _counter(registry, "serving_decode_steps_total") == 6 + 3
    assert _counter(registry, "serving_decode_steps_chained_total") == 5 + 2
    # an answer of one token takes no step at all
    assert len(engine.generate([3, 1, 4], max_new_tokens=1,
                               timeout=120).tokens) == 1
    assert _counter(registry, "serving_decode_steps_total") == 9


def test_sampling_path_counters_after_a_known_run(make_engine):
    """One answer at a time, so every step's level is its one request's:
    the two counters read what the schedule implies (an answer of n tokens
    takes n - 1 steps), and knobs that mean nothing ask for nothing."""
    registry = Registry()
    engine = make_engine(registry=registry)
    sampled = lambda: _counter(registry, "serving_decode_steps_sampled_total")
    sorted_ = lambda: _counter(registry, "serving_decode_steps_sorted_total")
    # (knobs, tokens, steps that sampled, steps that sorted)
    schedule = [
        (dict(), 7, 0, 0),
        (dict(top_k=5, top_p=0.5), 4, 0, 0),          # greedy: knobs ignored
        (dict(temperature=0.7, seed=5), 5, 4, 0),
        (dict(temperature=0.7, top_k=VOCAB, seed=5), 3, 2, 0),
        (dict(temperature=0.9, top_p=0.8, seed=77), 4, 3, 3),
        (dict(temperature=1.3, top_k=6, seed=9), 6, 5, 5),
        (dict(), 3, 0, 0),                            # and back to the argmax
        (dict(temperature=0.9, top_k=6, seed=1), 1, 0, 0),  # no step at all
    ]
    steps = want_sampled = want_sorted = 0
    for knobs, new, more_sampled, more_sorted in schedule:
        assert len(engine.generate([3, 1, 4], max_new_tokens=new, timeout=120,
                                   **knobs).tokens) == new
        _idle(engine)
        steps += new - 1
        want_sampled += more_sampled
        want_sorted += more_sorted
        assert _counter(registry, "serving_decode_steps_total") == steps
        assert (sampled(), sorted_()) == (want_sampled, want_sorted)
    assert engine._decode._cache_size() == 1


def test_a_sampled_request_joins_a_greedy_batch_without_a_compile(lm, gated):
    """A greedy answer is a few steps in when a request that samples with a
    top-k is admitted beside it, and one of a single token that never
    steps: the steps they share are at the sampling slot's level, the rest
    take the argmax, the greedy tokens are ``generate``'s, and the one
    compiled step served all of it."""
    module, params, _ = lm
    engine, registry, gate = gated(3)
    engine.generate([2, 7], max_new_tokens=2, timeout=120)  # warm both programs
    _idle(engine)
    compiled = engine._decode._cache_size()
    before = _counter(registry, "serving_decode_steps_total")
    gate.reached.clear()
    greedy = engine.submit(GenerateRequest(prompt=[5, 9, 2], max_new_tokens=30))
    assert gate.reached.wait(60)
    warm = engine.submit(GenerateRequest(
        prompt=[4, 4], max_new_tokens=6, temperature=0.9, top_k=4, seed=3))
    lone = engine.submit(GenerateRequest(
        prompt=[8, 1], max_new_tokens=1, temperature=0.9, top_p=0.5, seed=4))
    gate.open()
    assert greedy.result(timeout=120).tokens == _ref(module, params,
                                                     [5, 9, 2], 30)
    assert len(warm.result(timeout=120).tokens) == 6
    assert len(lone.result(timeout=120).tokens) == 1
    _idle(engine)
    # the sampling request's five steps all fell among the greedy one's 29
    assert _counter(registry, "serving_decode_steps_total") - before == 29
    assert _counter(registry, "serving_decode_steps_sampled_total") == 5
    assert _counter(registry, "serving_decode_steps_sorted_total") == 5
    assert engine._decode._cache_size() == compiled == 1


# -------------------------------- the host must see the truth: the flushes


def test_cancel_with_a_step_in_flight_returns_what_the_device_made(
        lm, gated):
    module, params, _ = lm
    engine, registry, gate = gated(3)
    prompt = [6, 2, 8, 3]
    pending = engine.submit(GenerateRequest(prompt=prompt, max_new_tokens=20))
    assert gate.reached.wait(60)
    # three steps dispatched, the third unread; the host has two tokens
    assert engine.cancel(pending) is True
    gate.open()
    result = pending.result(timeout=60)
    assert result.finish_reason == "aborted"
    made = 1 + int(_counter(registry, "serving_decode_steps_total"))
    assert made >= 4
    assert result.tokens == _ref(module, params, prompt, 20)[:made]
    assert _counter(registry, "serving_tokens_total") == made
    stats = engine.stats()
    assert stats["active_slots"] == 0 and stats["pages_in_use"] == 0
    assert not engine._inflight


def test_stop_with_a_step_in_flight_returns_what_the_device_made(lm, gated):
    module, params, _ = lm
    engine, registry, gate = gated(4)
    prompt = [6, 2, 8, 3]
    pending = engine.submit(GenerateRequest(prompt=prompt, max_new_tokens=20))
    assert gate.reached.wait(60)
    stopper = threading.Thread(target=engine.stop)
    stopper.start()
    deadline = time.monotonic() + 30
    while engine._running and time.monotonic() < deadline:
        time.sleep(0.002)
    gate.open()
    stopper.join(60)
    result = pending.result(timeout=60)
    assert result.finish_reason == "aborted"
    made = 1 + int(_counter(registry, "serving_decode_steps_total"))
    assert made == 5  # the loop dispatched nothing more on its way out
    assert result.tokens == _ref(module, params, prompt, 20)[:made]
    assert _counter(registry, "serving_tokens_total") == made


def test_drain_waits_for_the_last_step_to_be_read(lm, make_engine):
    """The last step of an answer is dispatched and its slot given back by
    count: no slot is active, yet the answer is not on the host.  ``drain``
    may not return there."""
    module, params, _ = lm
    engine = make_engine()
    # the loop, about to read the last step, with the drain acknowledged
    gate = Gate(engine, "_read_behind", lambda engine, keep, t0: (
        keep == 0 and engine._drain_ack and len(engine._inflight) == 1
        and not engine._active.any()))
    prompt, new = [6, 2, 8, 3], 30
    pending = engine.submit(GenerateRequest(prompt=prompt, max_new_tokens=new))
    deadline = time.monotonic() + 60
    while not engine._active.any() and time.monotonic() < deadline:
        time.sleep(0.001)
    drained = []
    drainer = threading.Thread(
        target=lambda: drained.append((engine.drain(timeout=60),
                                       pending.done())))
    drainer.start()
    assert gate.reached.wait(60)
    time.sleep(0.1)  # some fifty of drain's polls
    assert drainer.is_alive() and not pending.done()
    gate.open()
    drainer.join(60)
    assert drained == [(True, True)]
    assert pending.result(0).tokens == _ref(module, params, prompt, new)
    assert pending.result(0).finish_reason == "length"
    engine.resume()


def test_hot_swap_with_a_step_in_flight_splits_old_from_new(lm, gated):
    """What is in flight when a swap is asked for ran on the old parameters
    and is answered whole; the request queued behind it gets the new."""
    module, params, params2 = lm
    engine, registry, gate = gated(3)
    prompt = [6, 2, 8, 3]
    old = engine.submit(GenerateRequest(prompt=prompt, max_new_tokens=9))
    assert gate.reached.wait(60)
    swapper = threading.Thread(
        target=lambda: engine.hot_swap(module, params2, timeout=60))
    swapper.start()
    deadline = time.monotonic() + 30
    while not engine.draining and time.monotonic() < deadline:
        time.sleep(0.002)
    queued = engine.submit(GenerateRequest(prompt=prompt, max_new_tokens=9))
    applying = Gate(engine, "_apply_swap", lambda engine: True)
    gate.open()
    # the old answer is whole and on the host before the parameters change
    assert applying.reached.wait(60)
    assert old.done() and not engine._inflight
    assert old.result(0).tokens == _ref(module, params, prompt, 9)
    applying.open()
    swapper.join(60)
    assert not swapper.is_alive()
    assert queued.result(60).tokens == _ref(module, params2, prompt, 9)
    assert old.result(0).tokens != queued.result(0).tokens
    assert _counter(registry, "serving_hot_swaps_total") == 1


def test_crash_hands_back_what_the_device_made(lm, make_engine):
    """A replica killed mid-decode had dispatched one step more than it had
    read: its request aborts with the prefill's token and every dispatched
    step's, a true prefix for the router to resume from."""
    module, params, _ = lm
    registry = Registry()
    engine = make_engine(registry=registry)
    prompt = [6, 2, 8, 3]
    chaos.configure("11:kill_replica=4")
    try:
        pending = engine.submit(
            GenerateRequest(prompt=prompt, max_new_tokens=20))
        result = pending.result(timeout=60)
    finally:
        chaos.configure(None)
    assert result.finish_reason == "aborted" and not engine.alive
    steps = int(_counter(registry, "serving_decode_steps_total"))
    assert 3 <= steps <= 5
    assert result.tokens == _ref(module, params, prompt, 20)[:1 + steps]
    assert _counter(registry, "serving_tokens_total") == 1 + steps
    assert not engine._inflight


# ------------------------------------------------- telemetry stays truthful


def test_ttft_is_stamped_when_the_token_is_on_the_host(lm, gated):
    """The first token is read one program behind: held there, a request has
    no first token yet, whatever the device has made."""
    module, params, _ = lm
    engine, registry, gate = gated(1)
    pending = engine.submit(GenerateRequest(prompt=[1, 2, 3],
                                            max_new_tokens=5))
    assert gate.reached.wait(60)
    state = engine._slots[0]
    assert state is not None and state.tokens == [] and state.ttft_s == 0.0
    held = time.perf_counter()
    time.sleep(0.05)
    gate.open()
    result = pending.result(60)
    assert result.ttft_s >= held - pending.enqueue_t + 0.05
    assert result.latency_s >= result.ttft_s
    assert registry.snapshot()["serving_ttft_seconds"]["count"] == 1


def test_one_observation_a_step_and_a_prefill(make_engine):
    """``serving_token_latency_seconds`` keeps one observation a decode step
    and ``serving_prefill_seconds`` one a prefill: the loop's own time in
    that call, so sums over counts stay what a step and a prefill cost."""
    registry = Registry()
    engine = make_engine(registry=registry)
    telemetry_before = telemetry.metrics.snapshot().get(
        "serving_token_latency_seconds")
    for new in (5, 3, 8):
        engine.generate([9, 9, 1], max_new_tokens=new, timeout=120)
    snap = registry.snapshot()
    assert snap["serving_token_latency_seconds"]["count"] == 4 + 2 + 7
    assert snap["serving_prefill_seconds"]["count"] == 3
    assert snap["serving_decode_steps_total"]["value"] == 13
    assert snap["serving_token_latency_seconds"]["sum"] > 0
    # nothing leaked onto the global registry
    assert telemetry.metrics.snapshot().get(
        "serving_token_latency_seconds") == telemetry_before


# ------------------------------------------------------------------ stress


def test_callers_and_cancels_from_many_threads_lose_no_token(lm, make_engine):
    """More callers than cores, a short switch interval, every fourth request
    cancelled at some point of its life: every whole answer is
    ``generate``'s, every partial one a prefix of it, and the engine counted
    exactly the tokens it handed back (an overrun or a double read would
    break the sum)."""
    module, params, _ = lm
    registry = Registry()
    engine = make_engine(registry=registry, queue_size=256)
    rng = np.random.default_rng(5)
    # few shapes: the lockstep reference compiles one program a shape
    jobs = [(rng.integers(0, VOCAB, size=int(n)).tolist(), int(new))
            for n, new in zip(rng.choice([3, 7], 48), rng.choice([1, 5, 12], 48))]
    refs = {(tuple(prompt), new): _ref(module, params, prompt, new)
            for prompt, new in jobs}
    results = [None] * len(jobs)

    def caller(i):
        prompt, new = jobs[i]
        pending = engine.submit(
            GenerateRequest(prompt=prompt, max_new_tokens=new))
        if i % 4 == 0:
            time.sleep(0.002 * (i % 5))
            engine.cancel(pending)
        results[i] = pending.result(timeout=120)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(len(jobs))]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(120)
        assert not any(thread.is_alive() for thread in threads)
    finally:
        sys.setswitchinterval(interval)
    handed = 0
    for (prompt, new), result in zip(jobs, results):
        assert result is not None
        ref = refs[(tuple(prompt), new)]
        handed += len(result.tokens)
        if result.finish_reason == "aborted":
            assert result.tokens == ref[:len(result.tokens)]
        else:
            assert result.finish_reason == "length" and result.tokens == ref
    assert any(r.finish_reason == "length" for r in results)
    assert engine.drain(timeout=60)
    engine.resume()
    assert _counter(registry, "serving_tokens_total") == handed
    assert _counter(registry, "serving_requests_total") == len(jobs)
    stats = engine.stats()
    assert stats["active_slots"] == 0 and stats["pages_in_use"] == 0
    assert not engine._inflight
