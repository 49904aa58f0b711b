"""The benchmark's files for the latent-attention expert configuration
(``sarvam_105b``): the FLOP count by hand, the readers of the block's
counters on hand-made marks, the configuration's file against what the
program is built with, and the cell's own files driven end to end at a tiny
size on the CPU (the widths swapped, as ``benchmark/test_serve.py`` swaps
GPT-2's), with the 8-bit control through the run's own ``judge``."""

import copy

import numpy as np
import pytest

CELL = "sarvam_105b.serve_closed_decode"

ASSIGNED = "serving_moe_assignments_total"
HELD = "serving_moe_assignments_held_total"
LOAD = "serving_moe_expert_load_max_over_mean"
STATE = "serving_state_per_position_bytes"


def test_forward_flops_by_hand(harness):
    """d 8, 2 heads of 3 + 2 (values 3), latent 4, 3 layers (1 dense of 16,
    2 expert layers of width 5: 8 experts, top 2, 4 held, 1 shared), vocab
    11; a prompt of 3 and 2 generated: 4 fed tokens, 10 attended pairs.
    Attention a token and layer: 8x2x5 + 8x6 + 4x2x6 + 2x3x8 = 224; dense
    3x8x16 = 384; an expert 3x8x5 = 120, an expert layer 64 (router) + 120
    (shared) + 2 x 4/8 x 120 = 304; a token 3x224 + 384 + 2x304 = 1,664.
    A pair and layer: 2 heads x (5 + 3) = 16."""
    count = harness.resolve(".", "serveflops_mla_moe:mla_moe_forward_flops")
    got = count(prompt=3, generated=2, vocab_size=11, hidden_size=8,
                num_hidden_layers=3, num_attention_heads=2, kv_lora_rank=4,
                qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=3,
                intermediate_size=16, moe_intermediate_size=5, num_experts=8,
                num_experts_per_tok=2, num_shared_experts=1,
                first_k_dense_replace=1, held_experts=4)
    assert got == 2 * 1664 * 4 + 2 * 8 * 11 * 2 + 2 * 3 * 16 * 10 == 14624


def test_the_cell_counts_the_issues_parameters(harness):
    """At the published widths a fed token's matrices are the ISSUE's
    arithmetic: attention 94.63M a layer, the dense layer's feed-forward
    201.33M, an expert layer 0.52M + 25.17M + 2 x 25.17M."""
    kwargs = harness.load_cell(CELL)["config_spec"]["serving"]["flops"]["kwargs"]
    count = harness.resolve(".", "serveflops_mla_moe:mla_moe_forward_flops")
    one = count(prompt=1, generated=1, **kwargs)   # one fed token, one pair
    two = count(prompt=2, generated=1, **kwargs)   # two fed, three pairs
    per_pair = 2 * 5 * 64 * (192 + 128)
    per_token = two - one - 2 * per_pair
    want = 5 * 94_633_984 + 201_326_592 + 4 * (524_288 + 3 * 25_165_824)
    assert per_token == 2 * want
    assert one == 2 * want + per_pair + 2 * 4096 * 65536


def _edge(assigned, held, load, state=5760.0, **others):
    return {ASSIGNED: assigned, HELD: held, LOAD: load, STATE: state,
            "serving_tokens_total": 1.0, **others}


# By hand: 10,000 assignments inside the window, 2,600 of them held; 50
# decode steps whose load ratios sum to 120.
MARKS = {
    "both_edges": {"open": _edge(2000.0, 500.0, (30.0, 10)),
                   "close": _edge(12000.0, 3100.0, (150.0, 60))},
    "first_touched_inside_the_window": {
        "open": {"serving_tokens_total": 0.0},
        "close": _edge(10000.0, 2600.0, (120.0, 50))},
    "one_edge_missing": {"close": _edge(10000.0, 2600.0, (120.0, 50))},
    "no_marks": None,
    # the parent of the PR that brought the counters, or GPT-2's block
    "a_program_without_the_counters": {
        "open": {"serving_tokens_total": 5.0},
        "close": {"serving_tokens_total": 9.0}},
    "nothing_routed_inside_the_window": {
        "open": _edge(7.0, 2.0, (3.0, 1)), "close": _edge(7.0, 2.0, (3.0, 1))},
}
WANT = {
    "moe_held_share": {"both_edges": 26.0,
                       "first_touched_inside_the_window": 26.0},
    "moe_load_max_over_mean": {"both_edges": 2.4,
                               "first_touched_inside_the_window": 2.4},
    "state_bytes_per_position": {"both_edges": 5760.0,
                                 "first_touched_inside_the_window": 5760.0,
                                 "nothing_routed_inside_the_window": 5760.0},
}


@pytest.mark.parametrize("case", sorted(MARKS))
@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_of_the_blocks_counters(harness, metric, case):
    """Found the way a run finds it, by the metric's file; None wherever
    there is nothing to read, and never an error."""
    reader = harness.resolve("readers", harness.metric_spec(metric)["reader"])
    got = reader({"marks": MARKS[case]})
    want = WANT[metric].get(case)
    assert got == (pytest.approx(want) if want is not None else None)


TILES = "serving_moe_tiles_total"
TOUCHED = "serving_moe_experts_touched_total"


def _walked(tiles, touched, **others):
    return {TILES: tiles, TOUCHED: touched, ASSIGNED: 1.0, **others}


# By hand: inside the window 3,000 held experts had a row in some call, and
# the walk ran 3,150 tiles (150 groups were longer than one tile).
TILE_MARKS = {
    "both_edges": ({"open": _walked(1050.0, 1000.0),
                    "close": _walked(4200.0, 4000.0)}, 1.05),
    "every_expert_read_once": ({"open": _walked(10.0, 10.0),
                                "close": _walked(70.0, 70.0)}, 1.0),
    "first_touched_inside_the_window": (
        {"open": {"serving_tokens_total": 0.0},
         "close": _walked(3150.0, 3000.0)}, 1.05),
    "one_edge_missing": ({"close": _walked(3150.0, 3000.0)}, None),
    "no_marks": (None, None),
    # the parent of the PR that brought the walk: it routes and counts its
    # assignments, and walks no tiles
    "a_program_without_the_counters": (
        {"open": {ASSIGNED: 5.0, HELD: 1.0},
         "close": {ASSIGNED: 9.0, HELD: 2.0}}, None),
    "no_call_inside_the_window": ({"open": _walked(7.0, 6.0),
                                   "close": _walked(7.0, 6.0)}, None),
}


@pytest.mark.parametrize("case", sorted(TILE_MARKS))
def test_the_reader_of_the_walks_counters(harness, case):
    """``moe_tiles_per_expert``, found by the metric's file: tiles that did
    work over experts touched, None wherever there is nothing to read."""
    reader = harness.resolve(
        "readers", harness.metric_spec("moe_tiles_per_expert")["reader"])
    marks, want = TILE_MARKS[case]
    got = reader({"marks": marks})
    assert got == (pytest.approx(want, rel=1e-12) if want is not None else None)


@pytest.mark.parametrize("case", ["both_edges",
                                  "a_program_without_the_counters"])
def test_the_line_is_made_with_and_without_the_walks_counters(harness, case):
    """Through ``harness.result_line`` in the metric's cell: on this
    program's marks the line carries the ratio; on the parent's (the driver
    lays this reader over a checkout whose product walks no tiles) the
    metric is left out and the line is made all the same."""
    marks, want = TILE_MARKS[case]
    manifest = harness.load_manifest()
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "moe_tiles_per_expert")
    assert entry == {
        "name": "moe_tiles_per_expert", "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "models",
        "moves": "serve_tokens_per_s",
        # a later expert configuration's cell reads the same counters
        "workloads": [CELL] + entry["workloads"][1:]}
    run = {"correct": True, "attempted": 5, "failed": 0,
           "facts": {"marks": marks}, "end_to_end": {},
           "device": {"platform": "tpu"}}
    line = harness.result_line(dict(manifest, per_layer=[entry]),
                               {"name": CELL}, run, True)
    if want is None:
        assert line["metrics"] == {}
    else:
        assert line["metrics"] == {"moe_tiles_per_expert": {
            "value": pytest.approx(want), "unit": "ratio"}}
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
        "moe_tiles_per_expert"}
    names = [m["name"] for m in manifest["per_layer"]]
    new = [m for m in manifest["per_layer"]
           if m["name"] in WANT or m["name"] == "moe_tiles_per_expert"]
    at = names.index(new[0]["name"])  # appended in a run, PR 32's after PR
    assert names[at:at + 4] == [m["name"] for m in new]  # 31's; later PRs' behind
    assert all(m["workloads"][0] == CELL and m["layer"] == "models"
               and m["moves"] == "serve_tokens_per_s" for m in new)


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_the_manifests_lines_and_names_have_the_drivers_form(harness, group):
    """The driver refuses the whole file for one ``why`` of 201 characters,
    and ``selftest.py files`` measures only the cells': every ``why``,
    ``layer`` and ``source`` is one printable line of 1 to 200, every name at
    most 64 of the name's characters, every entry just its keys."""
    import re
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source",
                           "workloads"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}}[group]
    for entry in harness.load_manifest()[group]:
        assert set(entry) <= keys, entry["name"]
        for key in ("why", "layer", "source"):
            line = entry.get(key, "x")
            assert 1 <= len(line) <= 200 and line.isprintable(), (
                entry["name"], key, len(line))
        names = [entry["name"], entry.get("config", "x"),
                 entry.get("traffic", "x"), *entry.get("reduced", ())]
        assert all(name.fullmatch(n) for n in names), names
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry.get("unit", "x"))


def test_the_configuration_is_the_published_one_cut_as_it_says(harness):
    """Every width, the router's 128 outputs, top-8 and all 64 heads as
    published; the three cut keys as run, with the published value beside;
    what the program is built with says the same."""
    spec = harness.load_cell(CELL)["config_spec"]
    published = {
        "first_k_dense_replace": 1, "head_dim": 576, "hidden_size": 4096,
        "intermediate_size": 16384, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "v_head_dim": 128, "default_theta": 10000}
    assert {k: spec[k] for k in published} == published
    assert spec["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}
    assert spec["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (spec["num_hidden_layers"], spec["num_experts"],
            spec["vocab_size"]) == (5, 32, 65536)
    assert spec["source_config"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    built = spec["model"]["kwargs"]
    assert built["num_experts"] == 128  # the router keeps its width
    assert built["held_experts"] == [0, spec["num_experts"]]
    assert built["rope_scaling"] == spec["rope_scaling"]
    for key in ("hidden_size", "num_attention_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "num_shared_experts",
                "first_k_dense_replace", "routed_scaling_factor",
                "rms_norm_eps", "rope_theta", "num_hidden_layers",
                "vocab_size"):
        assert built[key] == spec[key], key
    traffic = harness.load_cell(CELL)["traffic_spec"]
    engine = traffic["engine_kwargs"]
    assert engine["pages_per_slot"] * engine["page_size"] == built["max_len"]
    assert traffic["clients"] == engine["num_slots"] * 3 // 2 == 96


# ----------------------------------------------- the cell's files, driven tiny

TINY = dict(
    vocab_size=512, max_len=128, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, intermediate_size=128,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    num_shared_experts=1, first_k_dense_replace=1, routed_scaling_factor=2.5,
    rms_norm_eps=1e-6, rope_theta=10000, held_experts=[0, 2],
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1, "original_max_position_embeddings": 32,
                  "type": "deepseek_yarn"})
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
    # the tiny router (8 experts, width 64) falls the other way now and then,
    # so the rehearsal's limits stand well over nought (the chip's, at the
    # cell's own size, are in the configuration file)
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
            "state_bytes_per_position", "moe_tiles_per_expert"} <= set(traced)
    assert traced["serve_compiles_in_window"]["value"] == 0
    assert 0 < traced["moe_held_share"]["value"] < 100
    assert traced["moe_load_max_over_mean"]["value"] >= 1.0
    assert traced["moe_tiles_per_expert"]["value"] >= 1.0
    # three layers of one 24-wide float32 row
    assert traced["state_bytes_per_position"]["value"] == 3 * 24 * 4
    assert 0 < traced["serve_mfu"]["value"] < 100


def test_the_8bit_control_comes_out_as_not_correct(harness):
    """The control, both operands of every product rounded to
    ``float8_e4m3fn``, goes through the run's own ``judge`` and comes out as
    not correct; the reference's own greedy tokens come out as correct."""
    import servechecks

    reference = harness.load_module(".", "reference_mla_moe")
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
