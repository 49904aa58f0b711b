"""The benchmark's own checks (``python benchmark/selftest.py <name>``), one
case a check, each in a process of its own as a builder runs it: BENCHMARK.json,
the metric files, their readers and PERF.md's layers say the same, the FLOP
counts by hand, the watcher, ``correct``, the reducer and its readers on the
hand-made trace, the ``train_job`` driver end to end on a tiny model (the
result line's keys), a cut configuration added by files alone, and the
command's refusal of a machine without the chip (non-zero exit, no result
line).  ``serve`` is not run here (ROADMAP.md D18)."""

import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CHECKS = ("files", "flops", "watcher", "correct", "trace", "rehearsal",
          "cut_config", "refusal")


@pytest.mark.parametrize("check", CHECKS)
def test_selftest_check_passes(check):
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "selftest.py"), check],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]
    said = done.stdout.splitlines()
    assert said[-1] == "selftest ok"
    assert said[-2].startswith(f"{check}: ")
