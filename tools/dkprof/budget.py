"""Op grouping and the PERF.md-style budget computation.

Time attribution needs only the trace.  FLOP/s, roofline class, and MFU
additionally need to know how much arithmetic and traffic each group
represents — that comes from an optional *meta* dict (the
``dkprof_meta.json`` sidecar bench.py drops next to a capture, or CLI
flags): ``peak_flops`` / ``peak_bw`` for the chip ceilings (defaults:
TPU v5e, 197e12 bf16 FLOP/s and 819e9 B/s per PERF.md) and optional
``flops`` / ``bytes`` dicts keyed by group name.

Two PERF.md protocol rules are baked in (see its §4):

* ``%while``-parented scan bodies are excluded — they double-count the
  ops they contain;
* C++ infra frames (names containing ``::``) are never ops.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

__all__ = ["classify_op", "op_budget"]

#: default chip ceilings (TPU v5e: Google Cloud "TPU v5e" documentation)
DEFAULT_PEAK_FLOPS = 197e12
DEFAULT_PEAK_BW = 819e9

# Ordered HLO base-name prefixes -> group; first match wins, so the more
# specific spellings (reduce-window vs reduce) come first.
_GROUP_PREFIXES = (
    ("collective", ("all-reduce", "all-gather", "all-to-all",
                    "reduce-scatter", "collective-permute", "send", "recv")),
    ("matmul", ("dot", "convolution", "conv", "cudnn", "gemm", "einsum")),
    ("reduction", ("reduce-window", "select-and-scatter", "reduce",
                   "sort", "topk", "argmax", "argmin")),
    ("rng", ("rng-bit-generator", "rng")),
    ("data-movement", ("copy-start", "copy-done", "copy", "transpose",
                       "reshape", "broadcast", "concatenate",
                       "dynamic-update-slice", "dynamic-slice", "slice",
                       "gather", "scatter", "pad", "bitcast", "iota",
                       "tuple", "get-tuple-element")),
    ("fusion", ("fusion", "loop_fusion", "input_fusion", "output_fusion")),
)

_BASE_RE = re.compile(r"^%?([A-Za-z0-9_.-]+)")


def classify_op(name: str) -> Optional[str]:
    """Group name for one HLO op, or ``None`` for a non-op event
    (infra frame, while-loop parent, metadata)."""
    if "::" in name:
        return None  # C++ infra frame (ThunkExecutor, dispatcher, ...)
    m = _BASE_RE.match(name.strip())
    if not m:
        return None
    base = m.group(1).lower()
    if base.startswith("while"):
        return None  # scan-body parent: double-counts its contents
    if "fusion" in base:
        # XLA names fusions after their root op (broadcast_maximum_fusion,
        # loop_fusion.3, ...) — the root prefix must not misfile them
        return "fusion"
    for group, prefixes in _GROUP_PREFIXES:
        for prefix in prefixes:
            if base.startswith(prefix):
                return group
    return "other"


def op_budget(events, meta: Optional[dict] = None) -> dict:
    """Aggregate op events into the budget.

    ``events``: ``[{"name", "duration_ps"[, "num_occurrences"]}, ...]``
    (what :mod:`.xplane` / :mod:`.chrome` produce).  Returns a JSON-safe
    dict with ``total_ms``, per-group rows sorted by time (``time_ms``,
    ``pct``, ``count``, top ``ops``, and — when meta covers the group —
    ``achieved_tflops`` / ``mfu`` / ``achieved_gbs`` / ``roofline``),
    and overall ``mfu`` when meta carries ``total_flops``.
    """
    meta = dict(meta or {})
    peak_flops = float(meta.get("peak_flops") or DEFAULT_PEAK_FLOPS)
    peak_bw = float(meta.get("peak_bw") or DEFAULT_PEAK_BW)
    group_flops: Dict[str, float] = {
        k: float(v) for k, v in (meta.get("flops") or {}).items()}
    group_bytes: Dict[str, float] = {
        k: float(v) for k, v in (meta.get("bytes") or {}).items()}
    ridge = peak_flops / peak_bw  # FLOP/byte where compute overtakes HBM

    per_op: Dict[str, dict] = {}
    for e in events:
        group = classify_op(e.get("name") or "")
        if group is None:
            continue
        dur = int(e.get("duration_ps") or 0)
        if dur <= 0:
            continue
        op = per_op.setdefault(e["name"], {
            "name": e["name"], "group": group, "time_ps": 0, "count": 0})
        op["time_ps"] += dur
        op["count"] += int(e.get("num_occurrences") or 1)

    groups: Dict[str, dict] = {}
    for op in per_op.values():
        g = groups.setdefault(op["group"], {
            "group": op["group"], "time_ps": 0, "count": 0, "ops": []})
        g["time_ps"] += op["time_ps"]
        g["count"] += op["count"]
        g["ops"].append(op)

    total_ps = sum(g["time_ps"] for g in groups.values())
    rows: List[dict] = []
    for g in sorted(groups.values(), key=lambda g: -g["time_ps"]):
        secs = g["time_ps"] / 1e12
        row = {
            "group": g["group"],
            "time_ms": round(secs * 1e3, 6),
            "pct": round(100.0 * g["time_ps"] / total_ps, 2) if total_ps
            else 0.0,
            "count": g["count"],
            "ops": [
                {"name": o["name"],
                 "time_ms": round(o["time_ps"] / 1e9, 6),
                 "count": o["count"]}
                for o in sorted(g["ops"], key=lambda o: -o["time_ps"])[:5]
            ],
        }
        flops = group_flops.get(g["group"])
        nbytes = group_bytes.get(g["group"])
        if flops is not None and secs > 0:
            row["achieved_tflops"] = round(flops / secs / 1e12, 3)
            row["mfu"] = round(flops / secs / peak_flops, 4)
        if nbytes is not None and secs > 0:
            row["achieved_gbs"] = round(nbytes / secs / 1e9, 2)
        if flops is not None and nbytes:
            row["roofline"] = ("compute-bound"
                               if flops / nbytes >= ridge else "hbm-bound")
        elif nbytes is not None:
            row["roofline"] = "hbm-bound"
        rows.append(row)

    out = {
        "total_ms": round(total_ps / 1e9, 6),
        "op_count": sum(o["count"] for o in per_op.values()),
        "distinct_ops": len(per_op),
        "peak_flops": peak_flops,
        "peak_bw": peak_bw,
        "groups": rows,
    }
    total_flops = meta.get("total_flops")
    if total_flops and total_ps:
        out["mfu"] = round(
            float(total_flops) / (total_ps / 1e12) / peak_flops, 4)
    return out
