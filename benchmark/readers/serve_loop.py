"""The serving loop's own account of its time, from the instruments that the
loop thread keeps whether or not telemetry is on (``facts["marks"]``: every
instrument of the run's registry at the window's two edges, under its own
name; a histogram's ``(sum, count)``, a counter's value).  One observation of
a ``serving_loop_*`` histogram is one span of the same name in the program's
flight-recorder ring (``serving.loop``, ``.dispatch``, ``.wait``, ``.emit``,
``.idle``), from the same two clock reads.  A program whose loop keeps no
such account, as the parent of the PR that added it, reads None everywhere.

``loop_host_ms`` also writes one note line, ``serve_loop``: the window's
seconds against the sums of iteration and idle seconds (the loop thread's
coverage of the window, which should read 97-100%), the iterations, programs,
starved dispatches and collections, and, from the ring as it stands when the
reader runs (the loop's last two or three seconds), the five longest
``serving.loop`` spans with their phases' milliseconds and any ``gc`` span
inside them: where a run's tail reads high, that line says which phase held
the pause."""

import harness

ITERATION = "serving_loop_iteration_seconds"
DISPATCH = "serving_loop_dispatch_seconds"
WAIT = "serving_loop_wait_seconds"
EMIT = "serving_loop_emit_seconds"
IDLE = "serving_loop_idle_seconds"
QUEUE_WAIT = "serving_queue_wait_seconds"
DISPATCHES = "serving_dispatches_total"
STARVED = "serving_dispatches_starved_total"
GC_PAUSE = "serving_gc_pause_seconds_total"
#: the phases of one ``serving.loop`` (``.prefill`` lies inside ``.admit``)
PHASES = ("admit", "prefill", "dispatch", "wait", "emit")
LONGEST = 5


def _edges(facts):
    """``(open, close)`` marks if both edges were marked and the program's
    loop keeps its account, else None."""
    marks = facts.get("marks") or {}
    if "open" not in marks or "close" not in marks:
        return None
    if ITERATION not in marks["close"]:
        return None
    return marks["open"], marks["close"]


def _histogram(edges, name):
    """``(seconds, observations)`` that the histogram gained between the
    edges.  An instrument that is first touched inside the window is not at
    its opening edge yet: it stood at nought there."""
    first, last = edges[0].get(name, (0.0, 0)), edges[1].get(name, (0.0, 0))
    return last[0] - first[0], last[1] - first[1]


def _counter(edges, name):
    return edges[1].get(name, 0.0) - edges[0].get(name, 0.0)


def _mean_ms(facts, name):
    edges = _edges(facts)
    if edges is None:
        return None
    seconds, count = _histogram(edges, name)
    return 1e3 * seconds / count if count else None


def _window_s(facts):
    t_open, t_close = facts["window"]
    return t_close - t_open


def _longest_iterations(since):
    """The longest ``serving.loop`` spans still in the program's ring that
    began after ``since`` (the window's opening: an earlier engine of the
    process is not this run's), each with the milliseconds of its phases
    (the spans that share its ``iter``) and the ``gc`` spans that lie inside
    it, on whatever thread."""
    try:
        from distkeras_tpu import telemetry
    except ImportError:
        return None
    take = getattr(telemetry.flightdeck.recorder, "spans", None)
    if take is None:
        return None
    spans = [s for s in take() if s["t0"] >= since]
    loops = sorted((s for s in spans if s["name"] == "serving.loop"),
                   key=lambda s: s["t0"] - s["t1"])[:LONGEST]
    ms = lambda s: 1e3 * (s["t1"] - s["t0"])
    rows = []
    for loop in loops:
        row = {key: loop["attrs"].get(key)
               for key in ("iter", "admitted", "active", "starved")}
        row["ms"] = ms(loop)
        row["phases_ms"] = {
            phase: sum(ms(s) for s in spans
                       if s["name"] == "serving.loop." + phase
                       and s["attrs"].get("iter") == row["iter"]
                       and loop["t0"] <= s["t0"] and s["t1"] <= loop["t1"])
            for phase in PHASES}
        row["gc_ms"] = [ms(s) for s in spans if s["name"] == "gc"
                        and loop["t0"] <= s["t0"] and s["t1"] <= loop["t1"]]
        rows.append(row)
    return {"serving_loop_spans_in_ring":
            sum(s["name"] == "serving.loop" for s in spans),
            "gc_spans_in_ring": sum(s["name"] == "gc" for s in spans),
            "collections_in_process": getattr(
                telemetry.trace, "gc_collections", None),
            "longest": rows}


def loop_host_ms(facts):
    """The loop thread's own work an iteration: the iterations' seconds less
    the seconds it waited for the device inside them, over the iterations.
    When it nears the step's device time the host is the bound.  (A wait
    under an idle pass, the read before a sleep, is subtracted too: an engine
    that sleeps often reads a little low.)"""
    edges = _edges(facts)
    if edges is None:
        return None
    seconds, iterations = _histogram(edges, ITERATION)
    idle_s, idle = _histogram(edges, IDLE)
    wait_s, waits = _histogram(edges, WAIT)
    window_s = _window_s(facts)
    note = {"window_s": window_s, "iteration_s": seconds, "idle_s": idle_s,
            "coverage_share": 100.0 * (seconds + idle_s) / window_s,
            "wait_s": wait_s, "iterations": iterations, "idle_passes": idle,
            "waits": waits, "programs": _counter(edges, DISPATCHES),
            "starved": _counter(edges, STARVED),
            "gc_pause_s": _counter(edges, GC_PAUSE)}
    ring = _longest_iterations(facts["window"][0])
    if ring:
        note.update(ring)
    harness.note(serve_loop=note)
    if not iterations:
        return None
    return 1e3 * (seconds - wait_s) / iterations


def loop_wait_share(facts):
    """The share of the window that the loop thread had to spare: blocked
    on the device with nothing else to do."""
    edges = _edges(facts)
    if edges is None:
        return None
    return 100.0 * _histogram(edges, WAIT)[0] / _window_s(facts)


def step_dispatch_ms(facts):
    """What a decode step costs the host before it can turn to reading."""
    return _mean_ms(facts, DISPATCH)


def emit_ms(facts):
    """The per-slot Python that handing one read program's tokens to their
    requests costs, over every program read (steps and prefills)."""
    return _mean_ms(facts, EMIT)


def queue_wait_ms(facts):
    """How long an admitted request stood in the queue."""
    return _mean_ms(facts, QUEUE_WAIT)


def dispatch_starved_share(facts):
    """Dispatches that found the device empty while the host worked, over
    all dispatches: the program's own reading, over the whole window, of
    what the device trace's idle share reads from one second of it."""
    edges = _edges(facts)
    if edges is None:
        return None
    programs = _counter(edges, DISPATCHES)
    if not programs:
        return None
    return 100.0 * _counter(edges, STARVED) / programs


def gc_pause_share(facts):
    """The share of the window that garbage collections held the
    interpreter while the loop was inside an iteration."""
    edges = _edges(facts)
    if edges is None:
        return None
    return 100.0 * _counter(edges, GC_PAUSE) / _window_s(facts)
