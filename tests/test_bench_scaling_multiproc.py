"""bench.py --scaling across REAL processes — the pod-day command rehearsal.

VERDICT r3 item 5: the 8->64 harness had never executed multi-process, so
the first pod attempt would have been its first run.  This launches bench.py
itself (not a stub) in two jax.distributed processes over a combined
8-device CPU mesh with rehearsal shapes: the full path —
coordination-service join, global-mesh engines, per-point chip
counting, process-0-only printing — executes end to end.
"""

import json
import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_two_process_sweep(mode_flag: str, fail_msg: str):
    """Launch bench.py --scaling in two jax.distributed processes over a
    combined 8-device CPU mesh; return (outs, process-0 JSON lines)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    coordinator = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(repo, "bench.py"),
             "--cpu", "4", mode_flag,
             "--config", "mnist_mlp_single",
             "--scaling", "--scaling-config", "mnist_mlp_single",
             "--distributed", "--coordinator", coordinator,
             "--num-processes", "2", "--process-id", str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env={**os.environ, "PYTHONPATH": repo}, cwd=repo,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(fail_msg + "\n" + "\n".join(outs))
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} rc={p.returncode}:\n{out}"
    lines = [json.loads(l) for l in outs[0].strip().splitlines()
             if l.startswith("{")]
    return outs, lines


@pytest.mark.slow
def test_scaling_two_processes_tiny():
    outs, lines = _run_two_process_sweep(
        "--tiny", "scaling rehearsal timed out")

    # only process 0 prints; its lines are the config result + the sweep
    assert not [l for l in outs[1].strip().splitlines() if l.startswith("{")], (
        "process 1 must not print results:\n" + outs[1]
    )
    by_metric = {l["metric"]: l for l in lines}
    sweep = by_metric["mnist_mlp_single_scaling_efficiency"]
    assert sweep["num_processes"] == 2
    assert sweep["num_chips"] == 8  # 2 processes x 4 devices, global mesh
    assert set(sweep["points_samples_per_sec_per_chip"]) == {"1", "2", "4", "8"}
    assert sweep["points_chips"]["8"] == 8
    cfg = by_metric["mnist_mlp_single_samples_per_sec_per_chip"]
    assert cfg["value"] > 0 and cfg["chips"] == 8


@pytest.mark.slow
def test_scaling_two_processes_calibrated():
    """Same two-process sweep with reps UNPINNED: every sub-mesh point's
    owners run _calibrate_reps, whose reps broadcast is a GLOBAL
    collective — a process owning none of the point's devices must join
    it (_join_reps_broadcast) or the owners block forever and the sweep
    dies at the deadman with zero points measured.  --tiny pins reps and
    never reaches that path, so this variant is the actual pod-day
    rehearsal for calibrated sweeps."""
    _, lines = _run_two_process_sweep(
        "--tiny-calibrate",
        "calibrated scaling rehearsal timed out (sub-mesh broadcast "
        "deadlock?)")
    sweep = next(l for l in lines
                 if l["metric"] == "mnist_mlp_single_scaling_efficiency")
    # every point measured — the sub-mesh points did not deadlock
    assert set(sweep["points_samples_per_sec_per_chip"]) == {"1", "2", "4", "8"}
    assert sweep["status"] == "ok", sweep
