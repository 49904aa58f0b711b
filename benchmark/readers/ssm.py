"""The Mamba layers' selective-scan kernels, from the device trace: the decode
step's ``ssm_step`` against its roofline, and the prefill's ``ssm_scan`` as a
share of the device's busy time.  A Pallas call is a ``custom-call`` named
after the kernel (``ssm_step.<n>``, one a Mamba layer of a step).  The
operations and bytes are ``serveflops_jamba.ssm_step_cost``'s; the peaks are
``peaks.json``'s.  A program without the kernels (another block, or the
parent of the PR that brought them) reads None."""

import re

import harness
import serveflops_jamba

STEP = re.compile(r"^custom-call ssm_step[.\d]*$")
SCAN = re.compile(r"^custom-call ssm_scan[.\d]*$")


def _seconds(trace, pattern):
    return sum(seconds for name, seconds in trace["named_s"].items()
               if pattern.search(name))


def _mamba_layers(model):
    return sum(i % model["attn_layer_period"] != model["attn_layer_offset"]
               for i in range(model["num_hidden_layers"]))


def ssm_scan_time_share(facts):
    """The prefills' scan kernel's device time over the slice's busy time."""
    trace = facts.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = _seconds(trace, SCAN)
    return 100.0 * seconds / trace["busy_s"] if seconds else None


def ssm_step_roofline(facts):
    """The step's scan kernel: the least time of its calls in the slice (one a
    Mamba layer of each decode step the slice holds, each the larger of its
    operations over peak FLOP/s and its bytes over peak bytes/s, every slot of
    the cell's engine) over their device time; a note says which bound
    applies."""
    trace = facts.get("trace")
    if not trace or not trace.get("epochs"):
        return None
    seconds = _seconds(trace, STEP)
    if not seconds:
        return None
    model = facts["cell"]["config_spec"]["model"]["kwargs"]
    slots = facts["cell"]["traffic_spec"]["engine_kwargs"]["num_slots"]
    cost = serveflops_jamba.ssm_step_cost(
        slots=slots, d_inner=model["mamba_expand"] * model["hidden_size"],
        d_state=model["mamba_d_state"])
    peaks = facts["peaks"]
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    calls = trace["epochs"] * _mamba_layers(model)
    least = calls * max(by_flops, by_bytes)
    harness.note(ssm_step_roofline_bound="compute" if by_flops >= by_bytes
                 else "memory", ssm_step_calls=calls, ssm_step_least_s=least,
                 ssm_step_device_s=seconds)
    return 100.0 * least / seconds
