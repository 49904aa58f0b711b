"""What every driver and the command share: finding a cell's files by name,
claiming the chips, the caches, the notes and the result line."""

import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(path):
    with open(path) as handle:
        return json.load(handle)


def load_manifest():
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def load_cell(name):
    """``workloads/<name>.json`` with its configuration and traffic files
    read in beside it (``config_spec``, ``traffic_spec``)."""
    if not NAME.match(name):
        raise SystemExit(f"not a cell's name: {name!r}")
    cell = _json(os.path.join(HERE, "workloads", name + ".json"))
    cell["name"] = name
    cell["config_spec"] = _json(
        os.path.join(HERE, "configs", cell["config"] + ".json"))
    cell["traffic_spec"] = _json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell


def load_module(folder, module):
    """Import ``<folder>/<module>.py`` of the benchmark by file, so that a
    later PR adds a driver or a reader as a new file and nothing else."""
    if not NAME.match(module):
        raise SystemExit(f"not a module's name: {module!r}")
    path = os.path.join(HERE, folder, module + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{module}", path)
    loaded = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loaded)
    return loaded


def load_driver(kind):
    return load_module("drivers", kind)


def resolve(folder, target):
    """``"module:function"`` under ``<folder>/`` -> the function."""
    module, _, function = target.partition(":")
    return getattr(load_module(folder, module), function)


def claim_chips(chips):
    """The device line, or exit: JAX must see exactly ``chips`` TPU devices.
    Never another backend, never fewer chips than the cell was sized for."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] != chips:
        sys.exit(f"this cell needs exactly {chips} TPU chip(s); JAX found "
                 f"{device}.  No result.")
    return device


def enable_caches():
    """JAX's persistent compilation cache at the program's own fixed place
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    and every program kept in it, however quick its compile: after a
    checkout's first run of a cell nothing is compiled again."""
    import jax
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    path = enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def note(**fields):
    """One JSON object on a line of its own, before the result line."""
    print(json.dumps(fields), flush=True)


def peaks_for(device_kind):
    """The published peaks of this device; an unknown device is an error."""
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    kind = device_kind.lower()
    for entry in table:
        if any(key in kind for key in entry["device_kind_contains"]):
            return entry
    raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                     "benchmark/peaks.json: add the device with its source")


def metric_spec(name):
    return _json(os.path.join(HERE, "metrics", name + ".json"))


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def result_line(manifest, cell, run, traced):
    """The last line.  ``run`` is what the driver returned: ``correct``,
    ``attempted``, ``failed``, ``end_to_end`` (name -> value), ``device`` and
    ``facts`` (what the readers read).  Untraced, the metrics are the cell's
    end-to-end ones; traced, its per-layer ones, each from its own reader; a
    reader that finds nothing returns None and the metric is left out."""
    metrics = {}
    if not traced:
        for metric in manifest["end_to_end"]:
            if applies(metric, cell["name"]) and metric["name"] in run["end_to_end"]:
                metrics[metric["name"]] = {
                    "value": run["end_to_end"][metric["name"]],
                    "unit": metric["unit"]}
    else:
        for metric in manifest["per_layer"]:
            if not applies(metric, cell["name"]):
                continue
            spec = metric_spec(metric["name"])
            value = resolve("readers", spec["reader"])(run["facts"])
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics,
            "device": run["device"]}
    if traced and run.get("breakdown"):
        line["breakdown"] = run["breakdown"]
    if run.get("compared"):
        # each number that ``correct`` compared beside its limit, last
        line["compared"] = run["compared"]
    return line
