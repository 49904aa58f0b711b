"""The benchmark's files for the shortcut-expert configuration
(``longcat_flash_omni``): the FLOP count by hand, the two readers of the
zero-compute experts' counters on hand-made marks (with and without the
counters), the manifest's entries, the configuration's file against the
catalog's published config and against what the program is built with, and
the cell's own files driven end to end at a tiny size on the CPU (the widths
swapped, as ``benchmark/test_serve.py`` swaps GPT-2's), with the 8-bit
control through the run's own ``judge``."""

import copy

import numpy as np
import pytest

CELL = "longcat_flash_omni.serve_closed_reasoning"

ASSIGNED = "serving_moe_assignments_total"
ZERO = "serving_moe_assignments_zero_total"
REAL = "serving_moe_real_picks_max_over_mean"


def test_forward_flops_by_hand(harness):
    """d 8, 2 heads of 3 + 2 (values 3), latent 4, query latent 6, 3 double
    layers (dense 16, experts of width 5: 8 routed + 4 zero-compute outputs,
    top 3, 4 held), vocab 11; a prompt of 3 and 2 generated: 4 fed tokens,
    10 attended pairs.  One attention a token: 8x6 + 6x2x5 + 8x6 + 4x2x6 +
    2x3x8 = 252; a dense feed-forward 3x8x16 = 384; the branch 8x12 (router)
    + 3 x 4/12 x 120 (an expert is 3x8x5) = 216; a token and layer 2x252 +
    2x384 + 216 = 1,488.  A pair and layer: 2 attentions x 2 heads x (5 + 3)
    = 32."""
    count = harness.resolve(".", "serveflops_scmoe:scmoe_forward_flops")
    got = count(prompt=3, generated=2, vocab_size=11, hidden_size=8,
                num_layers=3, num_attention_heads=2, kv_lora_rank=4,
                q_lora_rank=6, qk_nope_head_dim=3, qk_rope_head_dim=2,
                v_head_dim=3, ffn_hidden_size=16, expert_ffn_hidden_size=5,
                n_routed_experts=8, zero_expert_num=4, moe_topk=3,
                held_experts=4)
    assert got == 2 * 3 * 1488 * 4 + 2 * 8 * 11 * 2 + 2 * 3 * 32 * 10 == 37984


def test_the_cell_counts_the_issues_parameters(harness):
    """At the published widths a fed token's matrices are the ISSUE's
    arithmetic: one attention 90.58M, one dense feed-forward 226.50M, the
    router 4.72M, an expert 37.75M at 12 x 16 / 768 = a quarter a token."""
    kwargs = harness.load_cell(
        CELL)["config_spec"]["serving"]["flops"]["kwargs"]
    count = harness.resolve(".", "serveflops_scmoe:scmoe_forward_flops")
    one = count(prompt=1, generated=1, **kwargs)   # one fed token, one pair
    two = count(prompt=2, generated=1, **kwargs)   # two fed, three pairs
    per_pair = 2 * 4 * 2 * 64 * (192 + 128)
    per_token = two - one - 2 * per_pair
    want = 4 * (2 * 90_570_752 + 2 * 226_492_416 + 4_718_592
                + 37_748_736 // 4)
    assert per_token == 2 * want
    assert one == 2 * want + per_pair + 2 * 6144 * 16384


def _edge(assigned, zero, real, **others):
    return {ASSIGNED: assigned, ZERO: zero, REAL: real,
            "serving_tokens_total": 1.0, **others}


# By hand: 12,000 assignments inside the window, 3,900 of them zero-compute;
# 40 decode steps whose ratios sum to 58.
MARKS = {
    "both_edges": {"open": _edge(3000.0, 1000.0, (14.5, 10)),
                   "close": _edge(15000.0, 4900.0, (72.5, 50))},
    "first_touched_inside_the_window": {
        "open": {"serving_tokens_total": 0.0},
        "close": _edge(12000.0, 3900.0, (58.0, 40))},
    "one_edge_missing": {"close": _edge(12000.0, 3900.0, (58.0, 40))},
    "no_marks": None,
    # the parent of the PR that brought the counters, or the other expert
    # block: it counts its assignments, and none of them zero-compute
    "a_program_without_the_counters": {
        "open": {ASSIGNED: 5.0, "serving_tokens_total": 5.0},
        "close": {ASSIGNED: 9.0, "serving_tokens_total": 9.0}},
    "nothing_routed_inside_the_window": {
        "open": _edge(7.0, 2.0, (3.0, 2)), "close": _edge(7.0, 2.0, (3.0, 2))},
}
WANT = {
    "moe_zero_share": {"both_edges": 32.5,
                       "first_touched_inside_the_window": 32.5},
    "moe_real_picks_max_over_mean": {"both_edges": 1.45,
                                     "first_touched_inside_the_window": 1.45},
}


@pytest.mark.parametrize("case", sorted(MARKS))
@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_of_the_zero_compute_counters(harness, metric, case):
    """Found the way a run finds it, by the metric's file; None wherever
    there is nothing to read, and never an error."""
    reader = harness.resolve("readers", harness.metric_spec(metric)["reader"])
    got = reader({"marks": MARKS[case]})
    want = WANT[metric].get(case)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("case", ["both_edges",
                                  "a_program_without_the_counters"])
def test_the_line_is_made_with_and_without_the_counters(harness, case):
    """Through ``harness.result_line`` in the cell: on this program's marks
    the line carries both metrics; on the parent's (the driver lays these
    readers over a checkout that has no such block) they are left out and
    the line is made all the same."""
    manifest = harness.load_manifest()
    entries = [m for m in manifest["per_layer"] if m["name"] in WANT]
    assert [e["name"] for e in entries] == [
        "moe_zero_share", "moe_real_picks_max_over_mean"]
    run = {"correct": True, "attempted": 5, "failed": 0,
           "facts": {"marks": MARKS[case]}, "end_to_end": {},
           "device": {"platform": "tpu"}}
    line = harness.result_line(dict(manifest, per_layer=entries),
                               {"name": CELL}, run, True)
    if case == "both_edges":
        assert line["metrics"] == {
            "moe_zero_share": {"value": pytest.approx(32.5), "unit": "%"},
            "moe_real_picks_max_over_mean": {"value": pytest.approx(1.45),
                                             "unit": "ratio"}}
    else:
        assert line["metrics"] == {}
    assert line["correct"] is True and line["attempted"] == 5


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
        "moe_held_share", "moe_load_max_over_mean", "state_bytes_per_position",
        "moe_tiles_per_expert", "moe_zero_share",
        "moe_real_picks_max_over_mean"}
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("moe_zero_share")  # appended, behind what stood there
    assert names[at:at + 2] == ["moe_zero_share",
                                "moe_real_picks_max_over_mean"]
    assert at > names.index("moe_tiles_per_expert")
    for metric in manifest["per_layer"][at:at + 2]:
        assert {k: v for k, v in metric.items() if k != "workloads"} == {
            "name": metric["name"], "unit": metric["unit"],
            "better": metric["better"], "source": "program_counter",
            "layer": "models", "moves": "serve_tokens_per_s"}
        assert metric["workloads"][0] == CELL  # a later cell comes behind
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "longcat_flash_omni", "serve_closed_reasoning")
    assert "longcat_flash_omni" in [c["name"] for c in manifest["configs"]]


def test_the_configuration_is_the_published_one_cut_as_it_says(harness):
    """Every number of the catalog row's ``config`` at the file's top level
    under the source's own key; the three cut keys as run, the published
    value beside; what the program is built with says the same, and the
    router keeps its 768 outputs."""
    spec = harness.load_cell(CELL)["config_spec"]
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    cut = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    assert {k: spec[k] for k in published} == dict(published, **cut)
    assert spec["reduced"] == list(cut)
    assert spec["source_config"] == {k: published[k] for k in cut}
    assert spec["deployment"]["chips_sharing_a_layer"] == 32
    assert spec["deployment"]["chips_in_all"] == 224
    assert set(spec["assumed"]) >= {"lora_scales", "routing", "expert_bias",
                                    "rotary", "max_len", "mtp"}
    built = spec["model"]["kwargs"]
    assert built["n_routed_experts"] == 512  # the router keeps its width
    assert built["n_routed_experts"] + built["zero_expert_num"] == 768
    assert built["held_experts"] == [0, spec["n_routed_experts"]]
    assert (built["vocab_size"], built["max_len"]) == (16384, 2048)
    for key in ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
                "num_layers", "num_attention_heads", "kv_lora_rank",
                "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
                "qk_nope_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
                "routed_scaling_factor", "rms_norm_eps", "rope_theta",
                "zero_expert_num", "moe_topk", "vocab_size"):
        assert built[key] == spec[key], key
    flops = spec["serving"]["flops"]["kwargs"]
    assert flops["held_experts"] == spec["n_routed_experts"]
    assert all(flops[k] == built[k] for k in flops if k != "held_experts")
    traffic = harness.load_cell(CELL)["traffic_spec"]
    engine = traffic["engine_kwargs"]
    assert engine["pages_per_slot"] * engine["page_size"] == built["max_len"]
    assert traffic["clients"] == engine["num_slots"] * 3 // 2
    assert (traffic["prompt_tokens"], traffic["output_tokens"]) == (
        {"law": "uniform", "low": 256, "high": 1024},
        {"law": "uniform", "low": 256, "high": 768})


# ----------------------------------------------- the cell's files, driven tiny

TINY = dict(
    vocab_size=512, max_len=128, hidden_size=64, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8, v_head_dim=8,
    qk_nope_head_dim=8, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=8, zero_expert_num=4,
    moe_topk=3, rms_norm_eps=1e-5, rope_theta=10000000, held_experts=[0, 2])
RULES = {"requests": 6, "tokens_at_least": 20, "gap_widest_at_most": 0.05,
         "gap_fourth_mean_at_most": 1e-10, "control": "float8_e4m3fn"}


@pytest.fixture
def tiny_cell(harness):
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell["config_spec"]["model"]["kwargs"] = dict(TINY)
    serving = cell["config_spec"]["serving"]
    serving["flops"]["kwargs"] = dict(
        {k: v for k, v in TINY.items() if k in serving["flops"]["kwargs"]},
        held_experts=TINY["held_experts"][1])
    # the weights come in bfloat16 and the program rounds a product's
    # operands to them, which the float32 reference does not: a near tie of
    # the tiny router (12 outputs, width 64) falls the other way now and
    # then, so the rehearsal's limits stand well over nought (the chip's, at
    # the cell's own size, are in the configuration file)
    serving["correct"] = dict(RULES, gap_widest_at_most=2.0,
                              gap_fourth_mean_at_most=0.05)
    cell["traffic_spec"].update(
        clients=6, requests_per_client=40, ramp_s=0.5,
        prompt_tokens={"law": "uniform", "low": 40, "high": 100},
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
    line = harness.result_line(harness.load_manifest(), tiny_cell, run, False)
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                    "tpot_p90_ms"}
    traced = harness.result_line(harness.load_manifest(), tiny_cell, run,
                                 True)["metrics"]
    assert {"decode_step_ms", "prefill_ms", "slot_occupancy", "serve_mfu",
            "goodput_share", "decode_kv_read_share", "decode_chained_share",
            "decode_argmax_share",
            "moe_held_share", "moe_load_max_over_mean",
            "state_bytes_per_position", "moe_tiles_per_expert",
            "moe_zero_share", "moe_real_picks_max_over_mean"} <= set(traced)
    assert traced["serve_compiles_in_window"]["value"] == 0
    assert 0 < traced["moe_held_share"]["value"] < 100
    assert 0 < traced["moe_zero_share"]["value"] < 100
    assert (traced["moe_held_share"]["value"]
            + traced["moe_zero_share"]["value"]) < 100  # the rest is absent
    assert 1.0 <= traced["moe_real_picks_max_over_mean"]["value"] <= 3.0
    assert traced["moe_tiles_per_expert"]["value"] >= 1.0
    # two double layers of two 24-wide float32 rows
    assert traced["state_bytes_per_position"]["value"] == 2 * 2 * 24 * 4
    assert 0 < traced["serve_mfu"]["value"] < 100


def test_the_8bit_control_comes_out_as_not_correct(harness):
    """The control, both operands of every product rounded to
    ``float8_e4m3fn``, goes through the run's own ``judge`` and comes out as
    not correct; the reference's own greedy tokens come out as correct."""
    import servechecks

    reference = harness.load_module(".", "reference_scmoe")
    sizes = dict(TINY, hidden_size=128, held_experts=[0, 4])
    rules = dict(RULES, tokens_at_least=100)
    weights = reference.make_weights(3, **sizes)
    ours, lower = [], []
    for start in (5, 205):
        sequence = list(range(start, start + 40))
        for _ in range(60):  # the reference's own greedy continuation
            tokens = np.zeros(sizes["max_len"], np.int32)
            tokens[:len(sequence)] = sequence
            logits = reference._reference_logits(weights, tokens)
            sequence.append(int(np.argmax(logits[len(sequence) - 1])))
        got = reference.served_gaps(weights, sequence[:40], sequence[40:],
                                    sizes["max_len"], "float8_e4m3fn")
        ours.append(got[0])
        lower.append(got[1])
    assert not servechecks.judge(np.concatenate(ours), rules)[1]
    compared, reasons = servechecks.judge(np.concatenate(lower), rules)
    assert reasons, compared
