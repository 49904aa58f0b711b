"""bench.py must stay runnable: every config builds its engine, run_config
emits the driver's JSON schema, a failure becomes one parseable JSON error
row per requested metric AND a non-zero exit, and a run that finds no
accelerator prints no row at all.  Tiny shapes on the faked CPU mesh — this
is a smoke test, not a measurement."""

import json

import numpy as np
import pytest

import bench


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """main() turns the persistent compile cache on for the process; keep
    in-process main() calls from switching it on for the rest of the suite."""
    from distkeras_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda _: "")


def test_every_config_builds_engine():
    for config in bench.CONFIGS:
        engine, batch, window, shape, int_data, classes = bench._engine_for(config)
        assert engine.num_workers >= 1
        assert batch > 0 and window > 0 and classes > 1


def test_run_config_schema(monkeypatch):
    # Shrink the measurement so it runs in seconds on CPU.
    engine, _, window, shape, int_data, classes = bench._engine_for("mnist_mlp_single")

    def tiny_engine_for(config, num_workers=None):
        return engine, 8, window, shape, int_data, classes

    monkeypatch.setattr(bench, "_engine_for", tiny_engine_for)
    out = bench.run_config("mnist_mlp_single", n_windows=1, reps=1, k=2)
    required = {"metric", "value", "unit", "vs_baseline", "spread_pct",
                "mfu", "mfu_xla", "chips", "protocol"}
    assert required <= set(out), out.keys()
    assert out["unit"] == "samples/sec/chip"
    assert out["value"] > 0
    assert out["spread_pct"] >= 0
    assert out["chips"] >= 1
    assert out["protocol"] == bench.PROTOCOL
    assert out["mfu"] is None  # CPU backend: no peak-FLOPs table entry
    # the record must say where it ran and where the wall time went
    assert out["platform"] == "cpu"
    assert out["device_kind"]
    assert set(out["phases"]) == {"data", "h2d", "step", "commit"}
    assert all(v >= 0 for v in out["phases"].values())
    assert out["phases"]["data"] > 0 and out["phases"]["step"] > 0
    json.dumps(out)  # driver requires one JSON line


def test_run_config_records_dynamics_gauges(monkeypatch):
    """DISTKERAS_DYNAMICS=1 bench run: the health summary rides in the
    emitted record next to "phases" and lands in the metrics registry."""
    from distkeras_tpu import telemetry

    telemetry.dynamics.configure(enabled=True, watchdog="off")
    try:
        engine, _, window, shape, int_data, classes = bench._engine_for(
            "mnist_mlp_single")
        monkeypatch.setattr(
            bench, "_engine_for",
            lambda config, num_workers=None:
            (engine, 8, window, shape, int_data, classes))
        out = bench.run_config("mnist_mlp_single", n_windows=1, reps=1, k=1)
    finally:
        telemetry.dynamics.configure()
    dyn = out["dynamics"]
    assert dyn["grad_norm"] > 0
    assert "update_norm" in dyn and "divergence_max" in dyn
    assert dyn["nonfinite_grads_max"] == 0
    assert telemetry.metrics.snapshot()["dynamics_grad_norm"]["value"] > 0
    json.dumps(out)  # still one JSON line for the driver


def test_vs_baseline_null_when_unpinned(monkeypatch, tmp_path):
    engine, _, window, shape, int_data, classes = bench._engine_for("mnist_mlp_single")
    monkeypatch.setattr(
        bench, "_engine_for",
        lambda config, num_workers=None: (engine, 8, window, shape, int_data, classes),
    )
    empty = tmp_path / "pins.json"
    empty.write_text(json.dumps({"configs": {}}))
    monkeypatch.setattr(bench, "BASELINE_FILE", str(empty))
    out = bench.run_config("mnist_mlp_single", n_windows=1, reps=1, k=1)
    assert out["vs_baseline"] is None  # not 1.0: unpinned must be distinguishable


def test_baseline_file_pins_every_config():
    pins = json.load(open(bench.BASELINE_FILE))
    assert isinstance(pins.get("configs"), dict)
    assert all(isinstance(v, (int, float)) for v in pins["configs"].values())
    assert bench.HEADLINE in pins["configs"], "headline config must be pinned"
    missing = [c for c in bench.CONFIGS if c not in pins["configs"]]
    assert not missing, f"every config must carry a real-TPU pin: {missing}"
    # VERDICT r3 weak #1: pins are only a regression signal under the
    # protocol they were measured with — the file must say which, and it
    # must be the harness's current one.
    assert pins.get("protocol") == bench.PROTOCOL, (
        f"pin protocol {pins.get('protocol')!r} != harness {bench.PROTOCOL!r}"
        " — re-pin with `python bench.py --config all --write-baseline`"
    )


def test_vs_baseline_refuses_cross_protocol_pins(monkeypatch, tmp_path):
    stale = tmp_path / "pins.json"
    stale.write_text(json.dumps({
        "protocol": "some-older-protocol/v1",
        "configs": {"mnist_mlp_single": 100.0},
    }))
    monkeypatch.setattr(bench, "BASELINE_FILE", str(stale))
    out = bench._vs_baseline_fields("mnist_mlp_single", 630.0)
    assert out["vs_baseline"] is None  # NOT 6.3: that number would be a lie
    assert "re-pin" in out["pin_error"]
    fresh = tmp_path / "pins2.json"
    fresh.write_text(json.dumps({
        "protocol": bench.PROTOCOL,
        "configs": {"mnist_mlp_single": 100.0},
    }))
    monkeypatch.setattr(bench, "BASELINE_FILE", str(fresh))
    out = bench._vs_baseline_fields("mnist_mlp_single", 630.0)
    assert out["vs_baseline"] == 6.3 and "pin_error" not in out


def test_write_baseline_roundtrip(monkeypatch, tmp_path):
    import jax

    target = tmp_path / "pins.json"
    monkeypatch.setattr(bench, "BASELINE_FILE", str(target))
    live_kind = jax.devices()[0].device_kind
    bench.write_baseline({"_device_kind": live_kind,
                          "mnist_mlp_single": 123.4})
    data = json.load(open(target))
    assert data["protocol"] == bench.PROTOCOL
    assert data["configs"] == {"mnist_mlp_single": 123.4}
    assert data["device_kind"] == live_kind
    # and the comparison path accepts what write_baseline wrote
    out = bench._vs_baseline_fields("mnist_mlp_single", 123.4)
    assert out["vs_baseline"] == 1.0
    # ...but refuses a pin taken on different hardware (unit-error class)
    data["device_kind"] = "TPU imaginary9000"
    json.dump(data, open(target, "w"))
    out = bench._vs_baseline_fields("mnist_mlp_single", 123.4)
    assert out["vs_baseline"] is None and "pin_error" in out


def test_calibration_path_runs_and_clears_programs(monkeypatch):
    # reps=None exercises the two-point calibration: it must produce a
    # sane rep count and leave ONLY the final timed program alive (a live
    # extra executable degrades steady-state TPU throughput — see
    # WindowedEngine.clear_program_cache).
    engine, _, window, shape, int_data, classes = bench._engine_for("mnist_mlp_single")
    monkeypatch.setattr(
        bench, "_engine_for",
        lambda config, num_workers=None: (engine, 8, window, shape, int_data, classes),
    )
    out = bench.run_config("mnist_mlp_single", n_windows=1, reps=None, k=1,
                           min_set_seconds=0.01)
    assert out["value"] > 0
    # the calibration programs (reps=1, reps=4) were evicted; only the final
    # multi-epoch program remains cached
    keys = list(engine._epoch_fns)
    assert len(keys) == 1 and keys[0][0] == "multi"


def test_analytic_flops_closed_form():
    # Hand-recomputed layer sums against the LAYER_SPECS table: any drift
    # between the model zoo and these formulas must be deliberate.
    fwd = lambda c: sum(bench._spec_fwd_flops(s) for s in bench.LAYER_SPECS[c])
    assert fwd("cifar_cnn_downpour") == (
        2 * 32 * 32 * 64 * 27 + 2 * 32 * 32 * 64 * 576
        + 2 * 16 * 16 * 128 * 576 + 2 * 16 * 16 * 128 * 1152
        + 2 * 8192 * 256 + 2 * 256 * 10
    )  # = 196,482,048
    assert fwd("mnist_mlp_single") == 2 * (784 * 500 + 500 * 250 + 250 * 125 + 125 * 10)
    assert fwd("mnist_cnn_downpour") == (
        2 * 28 * 28 * 32 * 9 + 2 * 14 * 14 * 64 * 288
        + 2 * 3136 * 128 + 2 * 128 * 10
    )
    assert fwd("imdb_textcnn_dynsgd") == 2 * 256 * 128 * 128 * (3 + 4 + 5) + 2 * 384 * 2
    # ResNet-20: ~81.6 MFLOPs forward (sanity band, exact value is the sum)
    assert 80e6 < fwd("cifar_resnet20_adag") < 83e6
    # bandwidth-bound specs carry no MACs but ARE in the table (the measured
    # ceiling pays their wall): embed for TextCNN, bn for ResNet-20
    kinds = {s[0] for s in bench.LAYER_SPECS["imdb_textcnn_dynsgd"]}
    assert "embed" in kinds
    kinds = {s[0] for s in bench.LAYER_SPECS["cifar_resnet20_adag"]}
    assert "bn" in kinds
    for config in bench.CONFIGS:
        assert bench.analytic_train_flops_per_sample(config) == 3.0 * fwd(config)


def test_layer_microbench_builds_every_spec_kind():
    """Each spec kind lowers to a runnable fwd+bwd program (tiny shapes —
    this is the machinery behind --mfu-ceiling, not a measurement)."""
    import jax

    for spec in [("conv", 4, 4, 8, 3, 3, 1), ("conv", 4, 4, 8, 3, 8, 2),
                 ("conv1d", 8, 8, 3, 8), ("dense", 16, 8),
                 ("embed", 50, 8, 12), ("bn", 4, 4, 8)]:
        p, x, fn = bench._layer_fwd_bwd(spec, batch=2, dtype=jax.numpy.float32)
        g = fn(p, x)
        gp = g[0] if isinstance(g, tuple) else g
        assert gp.shape == p.shape
        assert jax.numpy.isfinite(gp).all()  # dklint: disable=DK107


def test_layer_wall_descent_carry_stays_finite():
    """The chained-scan protocol's claim 'descent keeps the carried values
    bounded' must actually hold: with the sum-of-squares loss the larger
    dense specs diverged to NaN within 64 reps (review finding) — the mean
    loss keeps every spec's gradient inside the stability bound."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    p, x, fn = bench._layer_fwd_bwd(("dense", 8192, 256), batch=64,
                                    dtype=jnp.bfloat16)
    eps = jnp.asarray(1e-3, jnp.bfloat16)

    def body(carry, _):
        p, x = carry
        gp, gx = fn(p, x)
        return (p - eps * gp, x - eps * gx), None

    (p_out, x_out), _ = jax.jit(  # dklint: disable=DK102 — one-shot test
        lambda p, x: lax.scan(body, (p, x), None, length=64)
    )(p, x)
    assert jnp.isfinite(p_out.astype(jnp.float32)).all()
    assert jnp.isfinite(x_out.astype(jnp.float32)).all()


def test_layer_wall_chained_scan_measures_compute_not_dispatch():
    """The wall comes from k chained reps inside ONE compiled scan; it must
    be positive, finite, and far below the single-dispatch wall for a tiny
    layer (measuring per-dispatch overhead x layers instead can produce
    'ceilings' BELOW the measured whole-model MFU)."""
    import jax

    w = bench._layer_wall_seconds(("dense", 32, 16), batch=4,
                                  dtype=jax.numpy.float32, min_time=0.02)
    assert 0 < w < 0.02, w  # per-rep wall, not the whole timed set


def test_unknown_device_kind_raises():
    """Utilisation against a guessed peak is not a measurement: a device
    that is not in the table is an error, never a default."""
    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_flops("TPU v5e") == 197e12
    with pytest.raises(ValueError, match="no peak-FLOP/s entry"):
        bench._peak_flops("TPU imaginary9000")
    with pytest.raises(ValueError, match="no peak-FLOP/s entry"):
        bench._peak_flops("cpu")
    # ...so the ceiling mode cannot produce a row off an accelerator
    with pytest.raises(ValueError, match="no peak-FLOP/s entry"):
        bench.run_mfu_ceiling("mnist_mlp_single")


def test_mfu_withheld_when_crosscheck_disagrees():
    peak = 100e12
    sps = 1e5
    batch = 256
    analytic = bench.analytic_train_flops_per_sample("cifar_cnn_downpour")
    # Agreement (xla within 2x): mfu printed, cross-check alongside.
    ok = bench._mfu_fields("cifar_cnn_downpour", sps, batch, peak,
                           xla_step_flops=batch * analytic * 0.9)
    assert ok["mfu"] is not None and ok["mfu_xla"] is not None
    # Disagreement >2x (the round-2 scan-body undercount): mfu withheld,
    # both counts emitted for inspection.
    bad = bench._mfu_fields("cifar_cnn_downpour", sps, batch, peak,
                            xla_step_flops=batch * analytic / 140.0)
    assert bad["mfu"] is None
    assert bad["mfu_analytic"] is not None and bad["mfu_xla"] is not None
    # No cross-check available: the analytic number still stands (it is the
    # hand-derived one), with mfu_xla null.
    solo = bench._mfu_fields("cifar_cnn_downpour", sps, batch, peak, None)
    assert solo["mfu"] is not None and solo["mfu_xla"] is None


def test_run_streaming_schema(monkeypatch):
    engine, _, window, shape, int_data, classes = bench._engine_for("mnist_mlp_single")
    monkeypatch.setattr(
        bench, "_engine_for",
        lambda config, num_workers=None: (engine, 8, window, shape, int_data, classes),
    )
    out = bench.run_streaming("mnist_mlp_single", n_windows=2, reps=1, k=1)
    assert out["metric"] == "mnist_mlp_single_streaming_overhead"
    assert out["in_memory_samples_per_sec_per_chip"] > 0
    assert out["streaming_samples_per_sec_per_chip"] > 0
    assert out["value"] is not None and out["value"] < 1.0
    json.dumps(out)


def test_every_line_carries_an_at_a_glance_status(capsys):
    """The verdict lives in the line itself as well as in the exit code:
    success lines say status=ok, error lines status=error — including
    results that return an error field through the normal path (a scaling
    sweep with a dead point)."""
    assert json.loads(bench._ok_line({"metric": "m", "value": 1.0}))["status"] == "ok"
    dead_point = {"metric": "m", "value": None, "error": "1 scaling point(s) failed"}
    assert json.loads(bench._ok_line(dead_point))["status"] == "error"
    bench._emit_error("boom")
    assert json.loads(capsys.readouterr().out.strip())["status"] == "error"


def test_emit_error_is_parseable_json(capsys):
    bench._emit_error("TPU fell over")
    line = capsys.readouterr().out.strip()
    parsed = json.loads(line)
    assert parsed["metric"] == bench.HEADLINE_METRIC
    assert parsed["value"] is None and parsed["vs_baseline"] is None
    assert "TPU fell over" in parsed["error"]


def test_main_refuses_the_cpu_without_a_row(monkeypatch, capsys):
    """No accelerator and no --cpu: the run says why on stderr, prints no
    row under a device metric's name, and exits non-zero.  (The suite's own
    backend IS the CPU, so main() meets the real condition.)"""
    ran = []
    monkeypatch.setattr(bench, "run_config",
                        lambda config, **kw: ran.append(config))
    monkeypatch.setattr("sys.argv", ["bench.py"])
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert exit_info.value.code not in (0, None)
    assert "no accelerator" in str(exit_info.value.code)
    assert capsys.readouterr().out == ""
    assert ran == []


def test_cpu_rehearsal_is_explicit_and_smoke_sized(monkeypatch, capsys):
    """--cpu N is the one way to a CPU row: smoke shapes, and the row says
    where it ran.  (N matches the suite's mesh: the device count cannot
    change once a backend is live.)"""
    seen_kw = {}

    def fake_run_config(config, **kw):
        seen_kw.update(kw)
        return {"metric": f"{config}_samples_per_sec_per_chip", "value": 42.0,
                "platform": "cpu", "phases": {}, "chips": 8}

    monkeypatch.setattr(bench, "run_config", fake_run_config)
    monkeypatch.setattr("sys.argv", ["bench.py", "--cpu", "8"])
    bench.main()
    parsed = json.loads(capsys.readouterr().out.strip())
    assert parsed["status"] == "ok" and parsed["platform"] == "cpu"
    assert seen_kw == dict(n_windows=1, reps=1, k=1, batch_override=16,
                           window_override=2)


def test_main_starts_no_child_process_that_loads_jax(monkeypatch, capsys):
    """One process initialises its own backend once.  A child that imports
    JAX is a second load of the chip's library: on the chip it fails or
    hangs, because the parent holds the device."""
    import subprocess
    import sys

    children = []
    real_popen = subprocess.Popen

    class RecordingPopen(real_popen):
        def __init__(self, args, *a, **kw):
            children.append(args if isinstance(args, str) else list(args))
            super().__init__(args, *a, **kw)

    monkeypatch.setattr(subprocess, "Popen", RecordingPopen)
    monkeypatch.setattr("sys.argv", ["bench.py", "--cpu", "8", "--tiny",
                                     "--config", "mnist_mlp_single"])
    bench.main()
    row = json.loads(capsys.readouterr().out.strip())
    assert row["status"] == "ok" and row["value"] > 0
    pythons = [c for c in children
               if sys.executable in c or "python" in str(c)]
    assert pythons == [], f"bench.py started python children: {pythons}"
    assert not hasattr(bench, "preflight")
    assert not hasattr(bench, "_probe_subprocess")


def test_write_baseline_refused_without_a_profile_trace(monkeypatch, capsys,
                                                        tmp_path):
    """A pin nobody can audit is refused: an error row, no pin file, and a
    non-zero exit."""
    monkeypatch.delenv("DISTKERAS_PROFILE", raising=False)
    monkeypatch.setattr(bench, "BASELINE_FILE", str(tmp_path / "pins.json"))
    monkeypatch.setattr(bench, "require_accelerator", lambda: None)
    monkeypatch.setattr(
        bench, "run_config",
        lambda config, **kw: {"metric": "m", "value": 1.0})
    monkeypatch.setattr("sys.argv", ["bench.py", "--write-baseline"])
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert exit_info.value.code == 1
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    refusal = [l for l in lines if l.get("metric") == "write_baseline"]
    assert len(refusal) == 1
    assert "refused" in refusal[0]["error"]
    assert not (tmp_path / "pins.json").exists()


def test_main_emits_error_row_then_exits_nonzero(monkeypatch, capsys):
    monkeypatch.setattr(bench, "require_accelerator", lambda: None)

    def boom(config, **kw):
        raise RuntimeError("compile exploded")

    monkeypatch.setattr(bench, "run_config", boom)
    monkeypatch.setattr("sys.argv", ["bench.py"])
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert exit_info.value.code == 1
    parsed = json.loads(capsys.readouterr().out.strip())
    assert parsed["metric"] == bench.HEADLINE_METRIC
    assert parsed["status"] == "error"
    assert "compile exploded" in parsed["error"]


def test_main_one_row_per_requested_metric_then_exits_nonzero(monkeypatch,
                                                              capsys):
    """A failed phase does not take the later ones with it: every requested
    metric gets its one row, in order, and any error row makes rc != 0."""
    monkeypatch.setattr(bench, "require_accelerator", lambda: None)

    def boom(*a, **kw):
        raise RuntimeError("device fault")

    monkeypatch.setattr(bench, "run_config", boom)
    monkeypatch.setattr(bench, "run_streaming", boom)
    monkeypatch.setattr(
        bench, "run_scaling",
        lambda config, run_kw: {"metric": f"{config}_scaling_efficiency",
                                "value": 1.0})
    monkeypatch.setattr("sys.argv", ["bench.py", "--scaling", "--streaming"])
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert exit_info.value.code == 1
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert [(l["metric"], l["status"]) for l in lines] == [
        (bench.HEADLINE_METRIC, "error"),
        (f"{bench.HEADLINE}_scaling_efficiency", "ok"),
        (f"{bench.HEADLINE}_streaming_overhead", "error"),
    ]


def test_scaling_sweep_schema(monkeypatch):
    calls = []

    def fake_run_config(config, num_workers=None, **kw):
        calls.append(num_workers)
        return {"value": 100.0 * (0.95 ** (num_workers or 1)),
                "chips": num_workers or 1}

    monkeypatch.setattr(bench, "run_config", fake_run_config)
    out = bench.run_scaling("cifar_cnn_downpour")
    assert out["metric"] == "cifar_cnn_downpour_scaling_efficiency"
    assert out["num_chips"] == max(calls)
    assert 0 < out["value"] <= 1.0
    assert set(out["points_samples_per_sec_per_chip"]) == {str(c) for c in calls}
    assert set(out["points_chips"]) == {str(c) for c in calls}
    assert out["num_processes"] == 1
    json.dumps(out)


def test_deadman_emits_pending_verdicts_and_exits_nonzero():
    """A measurement that never returns (a hung compile or collective): the
    deadman must turn the hang into one error JSON line per pending metric
    and end the process with a non-zero exit code."""
    import subprocess
    import sys

    code = (
        "import time, bench\n"
        "d = bench._Deadman()\n"
        "d.arm(0.2, ['m1', 'm2'])\n"
        "time.sleep(30)\n"  # simulated hung XLA call
        "print('never reached')\n"
    )
    import os

    root = os.path.dirname(os.path.abspath(bench.__file__))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=25, cwd=root)
    assert proc.returncode == 1
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert [l["metric"] for l in lines] == ["m1", "m2"]
    assert all("hung mid-run" in l["error"] for l in lines)
    assert "never reached" not in proc.stdout


def test_deadman_disarm_cancels():
    """Subprocess like the sibling test: if disarm regresses, the stray
    timer os._exit()s the host process — in-process that would truncate
    the pytest run."""
    import os
    import subprocess
    import sys

    code = (
        "import time, bench\n"
        "d = bench._Deadman()\n"
        "d.arm(0.05, ['m'])\n"
        "d.disarm()\n"
        "time.sleep(0.3)\n"
        "print('survived')\n"
    )
    root = os.path.dirname(os.path.abspath(bench.__file__))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=25, cwd=root)
    assert proc.returncode == 0
    assert "survived" in proc.stdout
    assert "hung mid-run" not in proc.stdout


def test_scaling_line_reads_error_when_a_point_fails(monkeypatch):
    """A pod sweep must not read green over a broken point: run_scaling's
    own contract (its in-loop comment) and _ok_line's at-a-glance verdict.
    Simulate a 2-process sweep where the k=2 point dies on the measuring
    process — the emitted line must carry status: error, not ok."""
    import jax
    from jax.experimental import multihost_utils

    def fake_run_config(config, num_workers=None, **kw):
        if num_workers and num_workers > 1:
            raise RuntimeError("device fault at k=%d" % num_workers)
        return {"value": 100.0, "chips": 1}

    joins = []
    monkeypatch.setattr(bench, "run_config", fake_run_config)
    monkeypatch.setattr(bench, "_join_reps_broadcast",
                        lambda: joins.append(1))
    monkeypatch.setattr(jax, "device_count", lambda: 2)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "sync_global_devices",
                        lambda name: None)
    out = bench.run_scaling("mnist_mlp_single")
    assert out["point_errors"] == {"2": "RuntimeError: device fault at k=2"}
    line = json.loads(bench._ok_line(out))
    assert line["status"] == "error"
    assert "scaling point" in line["error"]
    # the pre-calibration failure joined the owners' global reps broadcast
    # (sub-mesh deadlock guard) exactly once
    assert joins == [1]
