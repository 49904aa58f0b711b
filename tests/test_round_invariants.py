"""Round-exit invariants (VERDICT r4 item 5): no committed test or README
sentence may reference an evidence artifact that is not committed.

Round 4 shipped three failures of exactly this shape — an enforcement test
whose artifact was never produced, a protocol-versioned pin never re-pinned,
and a README claiming an artifact that didn't exist.  This test makes that
class of failure visible at AUTHORING time: it scans every test source and
README.md for round-artifact filenames (``<NAME>_r<N>.json``) and asserts
each referenced file exists at the repo root.
"""

import glob
import json
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# mixed-case names too (a `BENCH_full_rNN` sweep file slips through an
# all-caps pattern — exactly the failure class this file exists to catch)
ARTIFACT_RE = re.compile(r"\b([A-Z][A-Za-z0-9_]*_r\d+\.json)\b")


def _missing_in(path):
    with open(path) as fh:
        names = set(ARTIFACT_RE.findall(fh.read()))
    return sorted(n for n in names
                  if not os.path.exists(os.path.join(REPO, n)))


def test_every_test_referenced_artifact_exists():
    missing = {}
    for path in sorted(glob.glob(os.path.join(REPO, "tests", "*.py"))):
        gone = _missing_in(path)
        if gone:
            missing[os.path.basename(path)] = gone
    assert not missing, (
        f"tests reference uncommitted artifacts: {missing} — land the "
        "artifact in the same commit as the test that demands it"
    )


def test_readme_and_perf_artifact_claims_are_true():
    missing = {}
    for doc in ("README.md", "PERF.md"):
        gone = _missing_in(os.path.join(REPO, doc))
        if gone:
            missing[doc] = gone
    assert not missing, (
        f"docs claim artifacts that do not exist: {missing} — documentation "
        "written ahead of evidence is how saturated artifacts shipped in r3"
    )


def test_committed_round_artifacts_parse_and_carry_results():
    """Every committed round artifact parses; sweeps/accuracy artifacts are
    non-empty.  BENCH_full_* files are JSON-lines (one metric per line, the
    harness's one-line-per-metric contract); the rest are single documents."""
    for path in sorted(glob.glob(os.path.join(REPO, "*_r[0-9][0-9].json"))):
        name = os.path.basename(path)
        with open(path) as fh:
            if name.startswith("BENCH_full"):
                lines = [json.loads(l) for l in fh if l.strip()]
                assert lines, f"{name}: empty sweep"
                assert all("metric" in l for l in lines), name
            else:
                data = json.load(fh)
                if name.startswith("ACCURACY"):
                    assert data.get("results"), f"{name}: empty results"
