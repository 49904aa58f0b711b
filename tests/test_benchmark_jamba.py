"""The benchmark's files for the Jamba configuration (``jamba2_3b``): the FLOP
count and the scan kernels' costs by hand, the two readers of the kernels'
device time on hand-made traces (with and without the kernels), the
manifest's entries, the configuration's file against the catalog's
published config and against what the program is built with, and the
cell's own files driven end to end at a tiny size on the CPU, with the
8-bit control through the run's own ``judge``."""

import copy
import json
import os

import numpy as np
import pytest

from test_jamba import TINY

CELL = "jamba2_3b.serve_closed_think"
# A JSON-lines catalog of published model configs, checked where given.
CATALOG = os.environ.get("MODEL_CONFIG_CATALOG", "")


def test_forward_flops_by_hand(harness):
    """d 8, three layers (Mamba, attention, Mamba: period 3, offset 1),
    d_inner 16, state 2, dt rank 3, 4 taps, 2 heads of 4 over 1 key-value
    head, dense width 5, vocab 11; a prompt of 3 and 2 generated: 4 fed
    tokens.  A Mamba mixer a token: 8x32 + 16x7 + 3x16 + 16x8 + 4x16 = 608;
    attention 8x8 + 2x8x4 + 8x8 = 192; every layer's dense 3x8x5 = 120.  A
    token: 2 x 608 + 192 + 3 x 120 = 1,768.  Pairs: 1+2+3+4 = 10 of 2 heads
    x 8 = 16.  The scan: 7 x 16 x 2 = 224 a token and Mamba layer, counted
    once (not a multiply-add)."""
    count = harness.resolve(".", "serveflops_jamba:jamba_forward_flops")
    got = count(prompt=3, generated=2, vocab_size=11, hidden_size=8,
                num_hidden_layers=3, attn_layer_period=3, attn_layer_offset=1,
                num_attention_heads=2, num_key_value_heads=1,
                intermediate_size=5, mamba_d_state=2, mamba_d_conv=4,
                mamba_expand=2, mamba_dt_rank=3)
    assert got == (2 * 1768 * 4 + 2 * 8 * 11 * 2 + 2 * 16 * 10
                   + 2 * 224 * 4) == 16608


def test_the_cell_counts_the_published_parameters(harness):
    """At the published widths a fed token's products are the row's
    arithmetic: a Mamba mixer's 41,241,792 parameters less its vectors (the
    convolution's bias, dt's bias, A_log, D and the inner norms: 15,552 in
    all; its taps are counted), an attention layer's 13,762,560, the dense
    62,914,560 a layer; the head (the embedding, 167,772,160) once a
    generated token."""
    kwargs = harness.load_cell(
        CELL)["config_spec"]["serving"]["flops"]["kwargs"]
    count = harness.resolve(".", "serveflops_jamba:jamba_forward_flops")
    one = count(prompt=1, generated=1, **kwargs)   # one fed token, one pair
    two = count(prompt=2, generated=1, **kwargs)   # two fed, three pairs
    per_pair = 2 * 20 * 2 * 128
    scan = 26 * 7 * 5120 * 16
    per_token = two - one - 2 * 2 * per_pair - scan
    mixer = 41_241_792 - (5120 + 5120 + 5120 * 16 + 5120 + 160 + 16 + 16)
    assert per_token == 2 * (26 * mixer + 2 * 13_762_560 + 28 * 62_914_560)
    assert one == per_token + 2 * per_pair + scan + 2 * 167_772_160


def test_the_scan_kernels_costs_by_hand(harness):
    """``ssm_step`` at 256 slots: 7 x 256 x 16 x 5,120 operations; float32
    bytes, the state read and written 2 x 256 x 16 x 5,120, x, Delta and y
    3 x 256 x 5,120, B and C 2 x 256 x 16, A and D 16 x 5,120 + 5,120: some
    184 MB, 224 us at 819 GB/s and 0.75 us at 197 TFLOP/s: memory-bound."""
    step = harness.resolve(".", "serveflops_jamba:ssm_step_cost")(
        slots=256, d_inner=5120, d_state=16)
    assert step["flops"] == 7 * 256 * 16 * 5120
    assert step["bytes"] == 4 * (2 * 256 * 16 * 5120 + 3 * 256 * 5120
                                 + 2 * 256 * 16 + 16 * 5120 + 5120)
    scan = harness.resolve(".", "serveflops_jamba:ssm_scan_cost")(
        positions=512, d_inner=5120, d_state=16)
    assert scan["flops"] == 7 * 512 * 16 * 5120
    assert scan["bytes"] == 4 * (3 * 512 * 5120 + 2 * 512 * 16
                                 + 2 * 16 * 5120 + 5120)


def _facts(harness, named, epochs=10, busy=1.0):
    return {"cell": harness.load_cell(CELL),
            "peaks": harness.peaks_for("TPU v5 lite"),
            "trace": {"named_s": named, "epochs": epochs, "busy_s": busy}}


#: by hand, at the cell's 256 slots: 10 decode steps x 26 Mamba layers at the
#: least time of 183.9 MB over 819 GB/s (224.5 us) is 58.37 ms of calls; the
#: trace holds 73.0 ms
STEP_BYTES = 4 * (2 * 256 * 16 * 5120 + 3 * 256 * 5120 + 2 * 256 * 16
                  + 16 * 5120 + 5120)
NAMED = {"custom-call ssm_step.7": 0.050, "custom-call ssm_step.81": 0.023,
         "custom-call ssm_scan.3": 0.012, "custom-call ssm_scan": 0.008,
         "fusion fusion.12": 0.5, "custom-call paged_decode_attention.2": 0.1}


def test_the_readers_of_the_scan_kernels(harness, capsys):
    """Found the way a run finds them, by the metrics' files."""
    read = lambda name, facts: harness.resolve(
        "readers", harness.metric_spec(name)["reader"])(facts)
    facts = _facts(harness, NAMED)
    least = 10 * 26 * STEP_BYTES / 819e9
    assert read("ssm_step_roofline", facts) == pytest.approx(
        100 * least / 0.073)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["ssm_step_roofline_bound"] == "memory"
    assert note["ssm_step_calls"] == 260
    assert read("ssm_scan_time_share", facts) == pytest.approx(2.0)


@pytest.mark.parametrize("case", ["no_trace", "no_kernels", "no_steps"])
def test_the_readers_read_nothing_where_there_is_nothing(harness, case):
    """None, never an error: no trace, a program without the kernels (the
    parent of the PR that brought them, or another block), a slice that
    holds no whole decode step."""
    facts = _facts(harness, {"fusion fusion.1": 0.4}
                   if case == "no_kernels" else NAMED,
                   epochs=0 if case == "no_steps" else 10)
    if case == "no_trace":
        facts["trace"] = None
    for name in ("ssm_step_roofline", "ssm_scan_time_share"):
        reader = harness.resolve("readers", harness.metric_spec(name)["reader"])
        got = reader(facts)
        if case == "no_steps" and name == "ssm_scan_time_share":
            assert got == pytest.approx(2.0)
        else:
            assert got is None


def test_the_manifest_lists_the_cell_under_what_it_reports(harness):
    manifest = harness.load_manifest()
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        "serve_tokens_per_s", "tpot_p90_ms", "decode_step_ms", "prefill_ms",
        "slot_occupancy", "prefill_padding_share", "goodput_share",
        "serve_mfu", "serve_compiles_in_window", "serve_device_idle_share",
        "serve_peak_hbm_gb", "decode_kv_read_share", "decode_chained_share",
        "decode_argmax_share", "loop_host_ms", "loop_wait_share",
        "step_dispatch_ms", "emit_ms", "queue_wait_ms",
        "dispatch_starved_share", "gc_pause_share",
        "state_bytes_per_position", "ssm_step_roofline",
        "ssm_scan_time_share"}
    # appended behind every entry before them (later PRs append behind)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("ssm_step_roofline")
    assert names[at:at + 2] == ["ssm_step_roofline", "ssm_scan_time_share"]
    assert at == names.index("prefill_attn_pairs_share") + 1
    for metric in manifest["per_layer"][at:at + 2]:
        assert metric["workloads"] == [CELL]
        assert (metric["unit"], metric["source"], metric["layer"],
                metric["moves"]) == ("%", "device_trace", "kernels",
                                     "serve_tokens_per_s")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "jamba2_3b", "serve_closed_think")
    assert manifest["workloads"].index(cell) == 6  # appended behind six
    config = manifest["configs"][5]
    assert (config["name"], config["reduced"]) == ("jamba2_3b", [])


def test_the_configuration_is_the_published_one(harness):
    """Every entry of the catalog row's ``config`` at the file's top level
    under the source's own key, nothing reduced; what the program is built
    with says the same, and the traffic's lengths fit the served window."""
    spec = harness.load_cell(CELL)["config_spec"]
    if CATALOG and os.path.exists(CATALOG):
        with open(CATALOG) as handle:
            row = next(r for r in map(json.loads, handle)
                       if r["name"] == "AI21-Jamba2-3B")
        assert spec["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert spec[key] == value, key
    assert spec["reduced"] == [] and "deployment" not in spec
    assert set(spec["assumed"]) >= {"layer_kinds", "inner_norms",
                                    "positions", "tied_head", "max_len",
                                    "weights"}
    built = spec["model"]["kwargs"]
    for key in built:
        if key != "max_len":
            assert built[key] == spec[key], key
    assert built["max_len"] == 2560
    flops = spec["serving"]["flops"]["kwargs"]
    assert all(flops[k] == built[k] for k in flops)
    traffic = harness.load_cell(CELL)["traffic_spec"]
    engine = traffic["engine_kwargs"]
    assert engine["pages_per_slot"] * engine["page_size"] == built["max_len"]
    assert traffic["clients"] == engine["num_slots"] * 3 // 2
    assert traffic["prompt_tokens"]["high"] + traffic["output_tokens"][
        "high"] <= built["max_len"]
    assert max(engine["prefill_buckets"]) == traffic["prompt_tokens"]["high"]


# ----------------------------------------------- the cell's files, driven tiny

RULES = {"requests": 6, "tokens_at_least": 20, "gap_widest_at_most": 0.05,
         "gap_fourth_mean_at_most": 1e-10, "control": "float8_e4m3fn"}


@pytest.fixture
def tiny_cell(harness):
    cell = copy.deepcopy(harness.load_cell(CELL))
    model = dict(TINY, vocab_size=512)
    cell["config_spec"]["model"]["kwargs"] = model
    serving = cell["config_spec"]["serving"]
    serving["flops"]["kwargs"] = {k: v for k, v in model.items()
                                  if k in serving["flops"]["kwargs"]}
    # the weights come in bfloat16 and the program rounds a product's
    # operands to them, which the float32 reference does not: a near tie
    # falls the other way now and then, so the rehearsal's limits stand well
    # over nought (the chip's, at the cell's own size, are in the
    # configuration file)
    serving["correct"] = dict(RULES, gap_widest_at_most=2.0,
                              gap_fourth_mean_at_most=0.05)
    cell["traffic_spec"].update(
        clients=6, requests_per_client=40, ramp_s=0.5,
        prompt_tokens={"law": "log_uniform", "low": 4, "high": 40},
        output_tokens={"law": "uniform", "low": 2, "high": 8},
        engine_kwargs={"num_slots": 4, "page_size": 8, "queue_size": 64,
                       "dtype": "float32"})
    cell["capture_s"] = 0.3
    return cell


def test_the_cells_files_drive_a_run_end_to_end(harness, tiny_cell):
    """The driver finds the model, the reference, the FLOP function and the
    readers by the names in the cell's own files."""
    import test_serve

    run, said = test_serve.drive(tiny_cell, seconds=1.5, trace=True)
    assert run["correct"], said[-3000:]
    assert run["attempted"] > 10 and run["failed"] == 0
    traced = harness.result_line(harness.load_manifest(), tiny_cell, run,
                                 True)["metrics"]
    assert {"decode_step_ms", "serve_mfu", "decode_kv_read_share",
            "state_bytes_per_position"} <= set(traced)
    assert traced["serve_compiles_in_window"]["value"] == 0
    # one attention layer's key and value of 16, float32
    assert traced["state_bytes_per_position"]["value"] == 2 * 16 * 4
    assert 0 < traced["serve_mfu"]["value"] < 100
    # a CPU trace has no device plane: the kernels' metrics read nothing
    assert not {"ssm_step_roofline", "ssm_scan_time_share"} & set(traced)


def test_the_8bit_control_comes_out_as_not_correct(harness):
    """The control, both operands of every product rounded to
    ``float8_e4m3fn`` and the rest (the scan's state among it) bfloat16,
    goes through the run's own ``judge`` and comes out as not correct; the
    reference's own greedy tokens come out as correct."""
    import servechecks

    reference = harness.load_module(".", "reference_jamba")
    sizes = dict(TINY, hidden_size=128)
    rules = dict(RULES, tokens_at_least=100)
    weights = reference.make_weights(3, **sizes)
    ours, lower = [], []
    for start in (5, 45):
        sequence = list(range(start, start + 14))
        for _ in range(50):  # the reference's own greedy continuation
            tokens = np.zeros(sizes["max_len"], np.int32)
            tokens[:len(sequence)] = sequence
            logits = reference._reference_logits(weights, tokens)
            sequence.append(int(np.argmax(logits[len(sequence) - 1])))
        got = reference.served_gaps(weights, sequence[:14], sequence[14:],
                                    sizes["max_len"], "float8_e4m3fn")
        ours.append(got[0])
        lower.append(got[1])
    assert not servechecks.judge(np.concatenate(ours), rules)[1]
    compared, reasons = servechecks.judge(np.concatenate(lower), rules)
    assert reasons, compared
