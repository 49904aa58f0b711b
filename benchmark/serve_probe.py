"""Readings that a serving cell's files rest on, taken on the chip by hand.

    python benchmark/serve_probe.py sizing --workload <cell> --sizings 16x64,24x64 --seed <n>
    python benchmark/serve_probe.py sweep --workload <cell> --rates 4,6,8 --seed <n>
    python benchmark/serve_probe.py control --workload <cell> --seeds 1,2,3 --seconds 8

``sizing`` runs the cell's traffic on one engine a sizing (slots x pages a
slot, ascending) and reads the decode step, the prefill, the rate, the
device's busy share of a capture and the peak: what a cell's ``engine_kwargs``
rest on.

``sweep`` finds the knee of an open-loop cell: one engine, the cell's traffic
at each of the rates in turn (ramp and a window of ``--seconds``), and for
each rate whether the queue grew.  The traffic file then states the knee and
the cell's rate as numbers.

``control`` reads what ``correct`` compares, for the program and for its
control, on several seeds in one process: for each seed the cell's own load
through the driver's own path, then the plain reference over the same
finished requests that a run takes, once for the served tokens and once for
the tokens that the reference computed in the configuration's
``serving.correct.control`` type (and in each of ``also_read``) puts first
at the same positions.  Each goes through the run's own ``judge``: the
control has to come out as not correct.  The limits stand between the
largest of the program's readings and the smallest of the control's.  The
benchmark's own runs call none of these.
"""

import argparse
import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(HERE), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import servechecks  # noqa: E402
import servegen  # noqa: E402


def _quarter_means(samples, key):
    values = [stats[key] for _, stats in samples]
    quarter = max(1, len(values) // 4)
    mean = lambda part: sum(part) / len(part) if part else None
    return mean(values[:quarter]), mean(values[-quarter:])


def sweep(cell, driver, rates, seed, seconds):
    """One engine, the cell's traffic at each rate in turn."""
    weights, _, registry, engine, widths = driver.build(cell, seed, seconds)
    model = cell["config_spec"]["model"]["kwargs"]
    try:
        driver.warm_up(engine, widths, model["max_len"], model["vocab_size"], seed)
        for rate in rates:
            traffic = dict(cell["traffic_spec"], rate_per_s=rate)
            requests = servegen.schedule(traffic, seed, seconds,
                                         vocab=model["vocab_size"],
                                         max_len=model["max_len"])
            load, sampler, _ = driver.drive(engine, registry, requests,
                                            traffic, seconds)
            summary = driver.summarise(load.records, load.t_open, load.t_close,
                                       load.deadline)
            queue = _quarter_means(sampler.samples, "queue_depth")
            active = _quarter_means(sampler.samples, "active_slots")
            step = sampler.marks["close"]["serving_token_latency_seconds"]
            first = sampler.marks["open"]["serving_token_latency_seconds"]
            harness.note(rate_per_s=rate, summary=summary,
                         queue_depth_first_last_quarter=queue,
                         active_slots_first_last_quarter=active,
                         decode_step_ms=1e3 * (step[0] - first[0])
                         / max(1, step[1] - first[1]),
                         sender_late_ms_max=1e3 * max(load.late_s, default=0.0))
    finally:
        engine.stop(timeout=30.0)


def sizing(cell, driver, sizings, seed, seconds, capture_s):
    """The cell's traffic on one engine a sizing (``slots x pages a slot``,
    ascending, so that the process's peak is each sizing's own): the decode
    step, the prefill, the rate and the device's busy share of a capture."""
    import shutil
    import tempfile

    import jax
    import tracelib

    model = cell["config_spec"]["model"]["kwargs"]
    for slots, pages in sizings:
        traffic = cell["traffic_spec"]
        traffic["engine_kwargs"].update(num_slots=slots, pages_per_slot=pages)
        if traffic["arrival"] == "closed":
            traffic["clients"] = slots + slots // 2
        weights, requests, registry, engine, widths = driver.build(
            cell, seed, seconds)
        trace_dir = tempfile.mkdtemp(prefix="bench-probe-")
        try:
            driver.warm_up(engine, widths, model["max_len"],
                           model["vocab_size"], seed)
            load, sampler, xplane = driver.drive(
                engine, registry, requests, traffic, seconds, trace_dir,
                capture_s)
        finally:
            engine.stop(timeout=30.0)
        reduced = (tracelib.reduce_file(xplane) if xplane else None) or {}
        shutil.rmtree(trace_dir, ignore_errors=True)
        del engine
        summary = driver.summarise(load.records, load.t_open, load.t_close,
                                   load.deadline)
        gained = lambda name: tuple(
            b - a for a, b in zip(sampler.marks["open"][name],
                                  sampler.marks["close"][name]))
        step, prefill = (gained(name) for name in driver.HISTOGRAMS)
        active = [stats["active_slots"] for _, stats in sampler.samples]
        harness.note(
            slots=slots, pages_per_slot=pages, clients=traffic.get("clients"),
            summary=summary,
            decode_step_ms=1e3 * step[0] / max(1, step[1]), steps=step[1],
            prefill_ms=1e3 * prefill[0] / max(1, prefill[1]),
            prefills=prefill[1],
            active_slots_mean=sum(active) / max(1, len(active)),
            busy_s=reduced.get("busy_s"), window_s=reduced.get("window_s"),
            epoch_module=reduced.get("epoch_module"),
            device_ops=(reduced.get("breakdown") or {}).get("device_ops"),
            memory_peak_bytes=(jax.local_devices()[0].memory_stats()
                               or {}).get("peak_bytes_in_use"))
        del weights, load, sampler


def control(cell, driver, seeds, seconds):
    """The program's readings and the control's, seed by seed."""
    import jax

    config = cell["config_spec"]
    model, rules = config["model"]["kwargs"], config["serving"]["correct"]
    for seed in seeds:
        weights, requests, registry, engine, widths = driver.build(
            cell, seed, seconds)
        try:
            driver.warm_up(engine, widths, model["max_len"],
                           model["vocab_size"], seed)
            load, _, _ = driver.drive(engine, registry, requests,
                                      cell["traffic_spec"], seconds)
        finally:
            engine.stop(timeout=30.0)
        del engine
        finished = [r for r in load.records if r["done"] is not None
                    and load.t_open <= r["due"] < load.t_close]
        records = servechecks.sample(finished, seed, rules["requests"])
        reading = servechecks.readings
        read = {"seed": seed, "finished": len(finished), "sampled": len(records)}
        for kind in [rules["control"]] + rules.get("also_read", []):
            program, lower = servechecks.read_gaps(
                weights, requests, records, model["max_len"], kind)
            # both go through the run's own ``judge``: the control has to
            # come out as not correct
            read["program"] = dict(
                reading(program), correct=not servechecks.judge(program, rules)[1])
            read["control " + kind] = dict(
                reading(lower), correct=not servechecks.judge(lower, rules)[1],
                reasons=servechecks.judge(lower, rules)[1])
        del weights, load
        read["bytes_in_use_after"] = (jax.local_devices()[0].memory_stats()
                                      or {}).get("bytes_in_use")
        harness.note(**read)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("what", choices=("sweep", "control", "sizing"))
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", default="")
    parser.add_argument("--seeds", default="")
    parser.add_argument("--sizings", default="",
                        help="slots x pages a slot, ascending: 16x64,32x64")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--ramp", type=float, default=None,
                        help="a ramp other than the traffic file's (control: "
                             "what is compared needs no steady state)")
    args = parser.parse_args(argv)
    cell = copy.deepcopy(harness.load_cell(args.workload))
    device = harness.claim_chips(cell["chips"])
    harness.note(probe=args.what, device=device,
                 compile_cache_dir=harness.enable_caches())
    driver = harness.load_driver(cell["traffic_spec"]["kind"])
    if args.ramp is not None:
        cell["traffic_spec"]["ramp_s"] = args.ramp
    if args.what == "sizing":
        sizing(cell, driver, [tuple(int(n) for n in part.split("x"))
                              for part in args.sizings.split(",")],
               args.seed, args.seconds, cell.get("capture_s", 3.0))
    elif args.what == "sweep":
        sweep(cell, driver, [float(r) for r in args.rates.split(",")],
              args.seed, args.seconds)
    else:
        control(cell, driver, [int(s) for s in args.seeds.split(",")],
                args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
