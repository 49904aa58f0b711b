"""The Pallas flash-attention kernel's share of device time and of its
roofline, from the device trace.  The operations and bytes are
``flops.flash_attention_cost``'s; the peaks are ``peaks.json``'s."""

import re

import flops
import harness


def _flash_seconds(facts):
    """Self time of the operations that the configuration's ``attention``
    group says are the kernel's (``trace_name``, a pattern over
    ``"<opcode> <operation's name>"``): on this libtpu a Pallas call is a
    ``custom-call`` named after the flax module that made it."""
    pattern = re.compile(facts["cell"]["config_spec"]["attention"]["trace_name"])
    return sum(seconds for name, seconds in facts["trace"]["named_s"].items()
               if pattern.search(name))


def flash_time_share(facts):
    trace = facts["trace"]
    if not trace or "attention" not in facts["cell"]["config_spec"]:
        return None
    seconds = _flash_seconds(facts)
    return 100.0 * seconds / trace["busy_s"] if seconds else None


def flash_roofline(facts):
    """Forward and backward together: each call's least time is the larger of
    its operations over peak FLOP/s and its bytes over peak bytes/s; a note
    on an earlier line says which bound applies to each.  The calls' shapes
    are the configuration's own: ``flops.kwargs`` (heads, dim, num_layers,
    seq) and ``training`` (batch, steps an epoch)."""
    trace, config = facts["trace"], facts["cell"]["config_spec"]
    if not trace or "attention" not in config or not trace["epochs"]:
        return None
    seconds = _flash_seconds(facts)
    if not seconds:
        return None
    peaks = facts["peaks"]
    model, training = config["flops"]["kwargs"], config["training"]
    cost = flops.flash_attention_cost(
        batch=training["trainer_kwargs"]["batch_size"], seq=model["seq"],
        heads=model["heads"], head_dim=model["dim"] // model["heads"])
    calls = (trace["epochs"] * training["windows_per_worker_per_epoch"]
             * training["trainer_kwargs"]["communication_window"]
             * model["num_layers"])
    least, bounds = 0.0, {}
    for name, part in cost.items():
        by_flops = part["flops"] / peaks["bf16_flops_per_s"]
        by_bytes = part["bytes"] / peaks["hbm_bytes_per_s"]
        least += calls * max(by_flops, by_bytes)
        bounds[name] = "compute" if by_flops >= by_bytes else "memory"
    harness.note(flash_roofline_bound=bounds, flash_calls_per_pass=calls,
                 flash_least_s=least, flash_device_s=seconds)
    return 100.0 * least / seconds
