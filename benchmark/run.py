"""The benchmark's one command: run one cell, print its result as the last line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: ``workloads/<cell>.json`` names
a configuration (``configs/``) and a traffic mix (``traffic/``); the traffic
file's ``kind`` names the driver (``drivers/<kind>.py``); the per-layer metrics
of ``BENCHMARK.json`` that apply to the cell each name their reader in
``metrics/<name>.json``.  This file holds no table of cells, models, metrics
or drivers.  ``README.md`` says how to add each.

It refuses any machine that does not hold exactly the cell's ``chips`` TPU
devices: non-zero exit, no result line.  Lines before the last are notes (JSON
objects too); only the last line is the result.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(HERE), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402  (benchmark/harness.py)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", metavar="DIR", default=None,
                        help="copy the profiler's files there (a look by hand)")
    args = parser.parse_args(argv)

    manifest = harness.load_manifest()
    cell = harness.load_cell(args.workload)
    device = harness.claim_chips(cell["chips"])  # exits unless chips x TPU
    cache_dir = harness.enable_caches()
    harness.note(start=args.workload, seed=args.seed, seconds=args.seconds,
                 trace=args.trace, device=device, compile_cache_dir=cache_dir)

    driver = harness.load_driver(cell["traffic_spec"]["kind"])
    run = driver.run(cell=cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_PROCESS_START,
                     device=device, keep_trace=args.keep_trace)
    print(json.dumps(harness.result_line(manifest, cell, run,
                                         bool(args.trace))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
