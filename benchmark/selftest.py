"""Checks of the benchmark's own parts, here on the CPU, with no chip.

    python benchmark/selftest.py            # all of them (about a minute)
    python benchmark/selftest.py files trace  # some, by name

Nothing printed here is a measurement: the rehearsals run a tiny model on the
CPU to prove the control flow, and their rates are not reported.  Not under
``tests/``: the repo's suite does not collect it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # touches no chip, whatever the machine

import contextlib
import copy
import glob
import io
import json
import math
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import checks  # noqa: E402
import flops  # noqa: E402
import harness  # noqa: E402
import tracelib  # noqa: E402
from watcher import Watcher, epoch_times  # noqa: E402

SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def near(got, want, rel=1e-9):
    assert math.isclose(got, want, rel_tol=rel, abs_tol=1e-12), (got, want)


# ------------------------------------------------------------------- files


def check_files():
    """Every data file loads; names, units and lengths are the contract's;
    BENCHMARK.json and the files say the same of every cell and metric."""
    manifest = harness.load_manifest()
    assert manifest["paths"] == ["benchmark"]
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end_to_end
    configs = {c["name"]: c for c in manifest["configs"]}
    for name, entry in configs.items():
        spec = harness._json(os.path.join(ROOT, entry["file"]))
        assert harness.NAME.match(name) and spec["name"] == name
        assert spec["source"] == entry["source"] and 1 <= len(entry["source"]) <= 200
        assert spec["reduced"] == entry["reduced"] == []
        harness.resolve(".", spec["flops"]["function"])(**spec["flops"]["kwargs"])
        harness.resolve(".", spec["data"]["maker"])
    four = 0
    for entry in manifest["workloads"]:
        cell = harness.load_cell(entry["name"])
        assert harness.NAME.match(entry["traffic"])
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == entry[key], (entry["name"], key)
        assert entry["config"] in configs and 1 <= len(entry["why"]) <= 200
        assert entry["chips"] in (1, 4)
        four += entry["chips"] == 4
        driver = harness.load_driver(cell["traffic_spec"]["kind"])
        # a kind of driver brings its own check of a cell's files; a driver
        # without one is a ``train_job``
        getattr(driver, "check_cell", check_train_cell)(
            cell, manifest["run_seconds"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    cells = {w["name"] for w in manifest["workloads"]}
    for path in glob.glob(os.path.join(HERE, "workloads", "*.json")):
        assert os.path.basename(path)[:-5] in cells, f"{path}: not in BENCHMARK.json"
    layers = set()
    reports = lambda name, cell: harness.applies(end_to_end[name], cell)
    for metric in manifest["end_to_end"]:
        assert set(metric.get("workloads", ())) <= cells
    for cell in cells:  # set-up time and at least one other, in every cell
        assert reports("setup_s", cell), cell
        assert sum(reports(name, cell) for name in end_to_end) >= 2, cell
    for metric in manifest["per_layer"]:
        spec = harness.metric_spec(metric["name"])
        assert set(spec) == {"reader", "what"}, metric["name"]
        assert set(metric.get("workloads", ())) <= cells
        assert metric["moves"] in end_to_end
        # every cell that a metric lists reports the metric that it moves
        for cell in metric.get("workloads", cells):
            assert reports(metric["moves"], cell), (metric["name"], cell)
        assert callable(harness.resolve("readers", spec["reader"]))
        layers.add(metric["layer"])
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert harness.NAME.match(metric["name"]), metric["name"]
        assert harness.UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for path in glob.glob(os.path.join(HERE, "metrics", "*.json")):
        name = os.path.basename(path)[:-5]
        assert any(m["name"] == name for m in manifest["per_layer"]), name
    with open(os.path.join(ROOT, "PERF.md")) as handle:
        perf = handle.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    harness.peaks_for("TPU v5 lite")
    try:
        harness.peaks_for("cpu")
    except SystemExit:
        pass
    else:
        raise AssertionError("an unknown device got peaks")
    # the serving driver's arithmetic, as far as it needs no model: the
    # repo's suite runs ``selftest.py files``, and so these with it
    import test_serve

    for test in test_serve.WITHOUT_A_MODEL:
        test()
    return (f"{len(cells)} cells, {len(manifest['per_layer'])} per-layer "
            f"metrics, {len(test_serve.WITHOUT_A_MODEL)} tests of the serving "
            "driver's arithmetic")


def check_train_cell(cell, run_seconds):
    """What ``train_job`` asks of a cell's files: a rate that sizes the job,
    and epochs enough for a period and a capture."""
    assert cell["rate_hint"] > 0
    driver = harness.load_driver(cell["traffic_spec"]["kind"])
    assert driver.plan(cell, run_seconds)["epochs"] >= 5


# ------------------------------------------------------------------- flops


def check_flops():
    """The CNN against bench.py's 0.59 GFLOP a row, GPT-2 small by hand."""
    cnn = harness._json(os.path.join(HERE, "configs", "cifar_cnn.json"))["flops"]
    got = flops.layer_stack_train_flops(**cnn["kwargs"])
    # bench.py::analytic_train_flops_per_sample("cifar_cnn_downpour") is 3 x
    # 196.5 M = 589.4 M; the stem's input gradient (2 x 1024 x 64 x 27) is
    # not needed and not counted here
    forward = (2 * 1024 * 64 * 27 + 2 * 1024 * 64 * 576 + 2 * 256 * 128 * 576
               + 2 * 256 * 128 * 1152 + 2 * 8192 * 256 + 2 * 256 * 10)
    near(got, 3 * forward - 2 * 1024 * 64 * 27)
    assert abs(got / 0.59e9 - 1) < 0.01, got
    lm = harness._json(os.path.join(HERE, "configs", "gpt2_small.json"))["flops"]
    got = flops.transformer_lm_train_flops(**lm["kwargs"])
    # by hand: 12 layers x 12 x 768^2 = 84,934,656 block parameters in
    # matmuls, head 768 x 50257 = 38,597,376; 6 x the sum = 741,192,192;
    # attention 12 layers x 4 x 768 x 512.5 pairs = 18,892,800 forward, x 3
    near(got, 6 * (84_934_656 + 38_597_376) + 3 * 18_892_800)
    cost = flops.flash_attention_cost(batch=8, seq=1024, heads=12, head_dim=64)
    # 96 (batch x heads) x 524,800 pairs x 64 x 2 = 6,448,742,400 a matmul
    near(cost["forward"]["flops"], 2 * 6_448_742_400)
    near(cost["backward"]["flops"], 4 * 6_448_742_400)
    near(cost["forward"]["bytes"], 4 * 8 * 1024 * 768 * 2 + 8 * 1024 * 12 * 4)
    return "cifar_cnn 0.586 GFLOP/row, gpt2_small 0.798 GFLOP/token"


# ----------------------------------------------------------------- watcher


def check_watcher():
    """Against a fake counter: every change stamped once, the final count
    read after the job, a swapped holder survived, a skipped epoch dated."""
    state = {"count": 0, "swap": False}

    def read():
        if state["swap"]:
            state["swap"] = False
            raise TypeError("int() argument must be ... not 'NoneType'")
        return state["count"]

    watcher = Watcher(read, poll_s=0.001).start()
    for count in (4, 8, 16):  # 12 is never seen: two epochs at once
        time.sleep(0.03)
        state["swap"] = count == 8
        state["count"] = count
    time.sleep(0.03)
    state["count"] = 20  # the final count, set as the job returns
    stamps = watcher.stop()
    assert [c for _, c in stamps] == [4, 8, 16, 20], stamps
    assert all(b[0] > a[0] for a, b in zip(stamps, stamps[1:]))
    done = epoch_times(stamps, 4, 6)
    assert done[2] == done[3] == stamps[2][0] and done[5] is None
    host = harness.load_module("readers", "host")
    p90 = host.epoch_ms_p90({"epoch_done": done[:5]})
    assert 10 < p90 < 200, p90

    def broken():
        raise RuntimeError("boom")

    failing = Watcher(broken, poll_s=0.001).start()
    time.sleep(0.01)
    try:
        failing.stop()
    except RuntimeError:
        pass
    else:
        raise AssertionError("the watcher swallowed its reader's error")
    return "4 changes stamped, skipped epoch dated, reader's error surfaced"


# ----------------------------------------------------------------- correct


def check_correct():
    """``correct`` fails on a lost commit, a non-finite loss, a loss that does
    not fall by the configuration's factor, and an untrained loss off ln V."""
    rules = {"last_over_first_at_most": 0.5}
    ok = lambda *a: checks.judge_training(*a)[0]
    assert ok([2.0, 1.2, 0.9], 3, 24, 24, rules)
    assert not ok([2.0, 1.2, 0.9], 3, 23, 24, rules), "a lost commit passed"
    assert not ok([2.0, 1.2, 0.9], 3, 25, 24, rules), "a double commit passed"
    assert not ok([2.0, float("nan"), 0.9], 3, 24, 24, rules)
    assert checks.judge_training([2.0, float("inf"), 0.9], 3, 24, 24, rules)[1] == 1
    assert not ok([2.0, 1.2], 3, 24, 24, rules), "a missing epoch passed"
    assert not ok([2.0, 1.5, 1.1], 3, 24, 24, rules), "a loss that fell too little passed"
    lm = {"last_over_first_at_most": 0.99, "first_loss_near": [10.825, 1.0]}
    assert ok([11.3, 10.9], 2, 16, 16, lm)
    assert not ok([9.0, 8.0], 2, 16, 16, lm)
    return "lost and double commits, nan, missing epoch, flat loss, wrong start: all fail"


# ------------------------------------------------------------------- trace

TRACE = os.path.join(HERE, "testdata", "small_trace.textproto")


def check_trace():
    """The reducer on the small trace of ``testdata/`` (two chips, made by
    hand in the shape of a v5e capture), against numbers worked out by hand.

    Epoch program on each chip: 0..400 (its beginning cut), 420..1420 (on chip
    1 ..1400), 1440..2440, 2460..2500 (its end cut: nothing follows it); the
    counter's copy at 410, 1425, 2445.  Real ends: 400, 1420 (1400), 2440, so
    the slice is [400, 2440) = 2040 ns, 2 whole epochs, 4 modules starting in
    it (2 copies + 2 epochs; the copy at 2445 is outside).
    Chip 0: two 1 ns copies plus while 420..1420 and 1440..2440: busy 2002.
    Chip 1: its first while ends at 1400: busy 1982.  Mean busy 1992, idle
    48 / 2040.  Collectives per epoch: all-reduce-start 10 + -done 100, x 2
    epochs = 220 on both chips.  The Pallas call: 200 x 2 = 400.  fusion.1:
    chip 0 (300 + 320) x 2 = 1240, chip 1 1220, mean 1230; fusion.2 70 x 2.
    The while's own time is 0: its children cover it.
    Gaps, chip 0: 400..410 under Wait for donation holds (402..409), 411..420
    under no host event, 1420..1425 and 1426..1440 under
    DevicePutWithSharding (1400..1438; the shorter PjitFunction covers less);
    chip 1 the same but 1400..1425.  Means: 29, 10, 9 ns."""
    ns = 1e-9
    got = tracelib.reduce_file(TRACE)
    assert got["chips"] == 2 and got["epochs"] == 2
    assert got["epoch_module"] == "jit_epoch_fn(1234)"
    near(got["modules_per_epoch"], 2.0)
    near(got["window_s"], 2040 * ns)
    near(got["busy_s"], 1992 * ns)
    assert [round(c["busy_s"] / ns) for c in got["per_chip"]] == [2002, 1982]
    near(got["collective_s"], 220 * ns)
    near(100 * got["collective_s"] / got["busy_s"], 100 * 220 / 1992)  # share
    named = {k: round(v / ns, 6) for k, v in got["named_s"].items()}
    assert named == {"fusion fusion.1": 1230, "fusion fusion.2": 140,
                     "custom-call _SelfAttention_0.5": 400,
                     "all-reduce-start all-reduce-start.1": 20,
                     "all-reduce-done all-reduce-done.1": 200,
                     "copy copy.9": 2}, named
    ops = [[n, round(v / ns, 6)] for n, v in got["breakdown"]["device_ops"]]
    assert ops == [["fusion", 1370], ["_SelfAttention_", 400],
                   ["all-reduce-done", 200], ["all-reduce-start", 20],
                   ["copy", 2]], ops
    gaps = [[n, round(v / ns, 6)] for n, v in got["breakdown"]["idle_gaps"]]
    assert gaps == [["DevicePutWithSharding", 29],
                    ["Wait for donation holds", 10], ["unattributed", 9]], gaps
    # the pieces, alone
    near(tracelib.union_ns([(0, 10), (5, 20), (30, 40), (35, 36)], 2, 38), 26)
    assert tracelib.gaps_of([(0, 10), (5, 20), (30, 40)], 2, 45) == [(20, 30), (40, 45)]
    assert tracelib.op_kind("%w.1 = (s32[], f32[2]{0}) while((s32[], f32[2]{0}) %t), body=%b") == "while"
    assert tracelib.op_kind("all-gather-start.3") == "all-gather-start"
    # the readers on it
    facts = {"trace": got, "peaks": harness.peaks_for("TPU v5 lite"),
             "cell": {"config_spec": {
                 "attention": {"trace_name": "^custom-call _SelfAttention_"},
                 "flops": {"kwargs": {"seq": 1024, "heads": 12, "dim": 768,
                                      "num_layers": 1}},
                 "training": {"windows_per_worker_per_epoch": 1,
                              "trainer_kwargs": {"batch_size": 8,
                                                 "communication_window": 1}}}}}
    device = harness.load_module("readers", "device")
    flash = harness.load_module("readers", "flash")
    near(device.device_idle_share(facts), 100 * 48 / 2040)
    near(device.dispatches_per_epoch(facts), 2.0)
    near(flash.flash_time_share(facts), 100 * 400 / 1992)
    # 2 epochs x 1 step x 1 layer = 2 passes; forward 12,897,484,800 ops /
    # 197e12 = 65.47 us (bytes 50,724,864 / 819e9 = 61.94 us: compute-bound),
    # backward twice the ops, 130.94 us (bytes 123.87 us: compute-bound)
    with contextlib.redirect_stdout(io.StringIO()) as said:
        share = flash.flash_roofline(facts)
    least = 2 * (12_897_484_800 / 197e12 + 25_794_969_600 / 197e12)
    near(share, 100 * least / (400 * ns))
    assert json.loads(said.getvalue())["flash_roofline_bound"] == {
        "forward": "compute", "backward": "compute"}
    # a capture too short for two ends is measured whole.  This one holds the
    # boundary between two epochs: the epoch program to 100 (operations
    # 10..60, 70..100), the counter's copy 110..112, the next epoch program
    # from 150 (an operation 150..195).  Window 10..195 = 185, busy 50 + 30 +
    # 2 + 45 = 127; between 100 and 150 the device idles 48 of 50 ns and 2
    # programs begin.  The programs' own idle rate is (185 - 127 - 48) / (185
    # - 50) = 10 / 135; over an epoch of 1000 ns by the watcher, idle is
    # 48 + 950 x 10 / 135 = 118.37 ns.
    from jax.profiler import ProfileData
    short = """
      planes { id: 1 name: "/device:TPU:0"
        lines { id: 1 name: "XLA Modules" timestamp_ns: 0
          events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
          events { metadata_id: 4 offset_ps: 110000 duration_ps: 2000 }
          events { metadata_id: 1 offset_ps: 150000 duration_ps: 50000 } }
        lines { id: 2 name: "XLA Ops" timestamp_ns: 0
          events { metadata_id: 2 offset_ps: 10000 duration_ps: 50000 }
          events { metadata_id: 3 offset_ps: 70000 duration_ps: 30000 }
          events { metadata_id: 5 offset_ps: 110000 duration_ps: 2000 }
          events { metadata_id: 2 offset_ps: 150000 duration_ps: 45000 } }
        event_metadata { key: 1 value { id: 1 name: "jit_epoch_fn(1)" } }
        event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
        event_metadata { key: 3 value { id: 3 name: "all-reduce.2" } }
        event_metadata { key: 4 value { id: 4 name: "jit_copy(2)" } }
        event_metadata { key: 5 value { id: 5 name: "copy.9" } } }
      """
    edge = tracelib.reduce_planes(ProfileData.from_text_proto(short).planes)
    assert edge["epochs"] == 0
    near(edge["window_s"], 185 * ns)
    near(edge["busy_s"], 127 * ns)
    near(edge["between"]["seconds"], 50 * ns)
    near(edge["between"]["idle_s"], 48 * ns)
    near(edge["collective_s"], 30 * ns)
    at_edge = dict(facts, trace=edge, traced_epoch=3,
                   epoch_done=[0.0, 1.0, 2.0, 3.0, 3.0 + 1000 * ns])
    near(device.device_idle_share(at_edge), 100 * (48 + 950 * 10 / 135) / 1000)
    near(device.dispatches_per_epoch(at_edge), 2.0)
    assert device.device_idle_share(dict(at_edge, traced_epoch=4)) is None
    # and one that holds no boundary (the next epoch's program never begins):
    # a slice inside one epoch program, no idle share, no modules per epoch
    inside = tracelib.reduce_planes(ProfileData.from_text_proto(
        short.replace("events { metadata_id: 1 offset_ps: 150000 duration_ps: 50000 }", "")
    ).planes)
    assert inside["epochs"] == 0 and inside["between"] is None
    assert device.device_idle_share(dict(at_edge, trace=inside)) is None
    assert device.dispatches_per_epoch(dict(at_edge, trace=inside)) is None
    for reader in (device.device_idle_share, device.dispatches_per_epoch,
                   flash.flash_time_share, flash.flash_roofline):
        assert reader(dict(facts, trace=None)) is None  # nothing to read
    return "busy union, idle share, collectives, modules, self times, gaps: as by hand"


# --------------------------------------------------------------- rehearsal


def tiny_cell(workers, rate_hint, capture_s=None):
    """The CNN cell's own files with the model and the sizes swapped for tiny
    ones and the traffic's worker count set (a tiny preset lives here, never
    in a cell)."""
    cell = copy.deepcopy(harness.load_cell("cifar_cnn.downpour_1chip"))
    cell["traffic_spec"]["trainer_kwargs"]["num_workers"] = workers
    cell.update(chips=workers, rate_hint=rate_hint, capture_s=capture_s)
    config = cell["config_spec"]
    config["model"] = {"import": "distkeras_tpu.models:MLP",
                       "kwargs": {"features": [32], "num_classes": 10}}
    config["data"]["kwargs"] = {"shape": [24], "classes": 10, "scale": 0.5}
    config["training"]["windows_per_worker_per_epoch"] = 3
    config["training"]["trainer_kwargs"].update(
        batch_size=16, communication_window=2, compute_dtype="float32")
    config["training"]["optimizer"]["knobs"]["learning_rate"] = 0.05
    config["correct"] = {"last_over_first_at_most": 0.9}
    return cell


def check_rehearsal():
    """The ``train_job`` driver end to end as a function: one worker
    untraced, four workers (on 4 virtual CPU devices) with a whole-epoch
    capture, one worker with a boundary capture (``capture_s``); the result
    line's keys.  A rehearsal, not a result."""
    import jax

    jax.config.update("jax_num_cpu_devices", 4)
    driver = harness.load_driver("train_job")
    manifest = harness.load_manifest()
    said = []
    for workers, rate_hint, capture_s in ((1, 200, False), (4, 20000, None),
                                          (1, 20000, 0.01)):
        traced = capture_s is not False
        cell = tiny_cell(workers, rate_hint, capture_s or None)
        device = {"platform": "cpu", "kind": "TPU v5 lite (described)",
                  "count": cell["chips"]}
        with contextlib.redirect_stdout(io.StringIO()):  # CPU times: not shown
            run = driver.run(cell=cell, seed=3_000_000_019, seconds=1.0,
                             trace=traced, t_start=time.perf_counter(),
                             device=device)
        job = run["facts"]["job"]
        assert run["correct"], "the tiny job's loss did not fall"
        assert run["attempted"] == job["epochs"] and run["failed"] == 0
        assert all(t is not None for t in run["facts"]["epoch_done"])
        assert run["facts"]["trace"] is None  # a CPU capture has no device plane
        line = harness.result_line(manifest, cell, run, traced)
        assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
        if traced:
            # the profiler was started and stopped inside the job
            assert run["facts"]["traced_epoch"] >= 3, run["facts"]["traced_epoch"]
            assert "compiles_in_window" in line["metrics"]
            assert "device_idle_share" not in line["metrics"]  # nothing to read
            assert not any(k in line["metrics"] for k in ("setup_s", "train_throughput"))
        else:
            assert set(line["metrics"]) == {"setup_s", "train_throughput"}
        json.dumps(line)
        said.append(f"{workers} worker(s), capture_s {capture_s}: "
                    f"{job['epochs']} epochs x {job['commits_per_epoch']} commits")
    return "; ".join(said)


def check_refusal():
    """The command itself refuses a machine without the chip: non-zero exit
    and no result line."""
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "cifar_cnn.downpour_1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0, "run.py ran without a chip"
    assert '"metrics"' not in done.stdout, done.stdout
    return f"exit {done.returncode}, no result line"


def check_serve():
    """The serving quarter: every test of ``test_serve.py`` (the schedule, the
    window's arithmetic, ``correct`` and its control, the driver end to end
    on a tiny model and with a token altered underneath)."""
    import test_serve

    names = sorted(name for name in vars(test_serve) if name.startswith("test_"))
    for name in names:
        getattr(test_serve, name)()
    return f"{len(names)} tests of test_serve.py"


CHECKS = {"files": check_files, "flops": check_flops, "watcher": check_watcher,
          "correct": check_correct, "trace": check_trace,
          "rehearsal": check_rehearsal, "serve": check_serve,
          "refusal": check_refusal}


def main(argv):
    for name in argv or list(CHECKS):
        print(f"{name}: {CHECKS[name]()}", flush=True)
    print("selftest ok")


if __name__ == "__main__":
    main(sys.argv[1:])
