"""The benchmark's files for the sliding-window expert configuration
(``mimo_v2_5``): the FLOP count by hand, the two readers of the block's
window counters on hand-made marks (with and without the counters), the
manifest's entries, the configuration's file against the catalog's published
config and against what the program is built with, and the cell's own files
driven end to end at a tiny size on the CPU (the widths swapped, as
``benchmark/test_serve.py`` swaps GPT-2's), with the 8-bit control through
the run's own ``judge``."""

import copy

import numpy as np
import pytest

from test_window_moe import TINY

CELL = "mimo_v2_5.serve_closed_longdoc"

READ = "serving_sliding_positions_read_total"
LIVE = "serving_sliding_positions_live_total"
PAIRS = "serving_prefill_attn_pairs_total"
CAUSAL = "serving_prefill_attn_causal_pairs_total"


def test_forward_flops_by_hand(harness):
    """d 8, three layers (global dense, sliding expert, global expert): a
    global layer 4 heads over 2 key-value heads of 3 (values 2), a sliding
    one 4 over 4, a window of 2; dense width 16, experts of width 5 (8
    routed, top 2, 4 held), vocab 11; a prompt of 3 and 2 generated: 4 fed
    tokens.  Projections a token: global 8x4x3 + 8x2x5 + 4x2x8 = 240,
    sliding 8x4x3 + 8x4x5 + 4x2x8 = 320; the dense layer 3x8x16 = 384; the
    expert layer's router 8x8 + 2 x 4/8 x 120 = 184.  A token: 240 + 384 +
    320 + 184 + 240 + 184 = 1,552.  Pairs: a global layer 1+2+3+4 = 10 of
    4 x 5 = 20 a pair, the sliding one 1+2+2+2 = 7 of 20."""
    count = harness.resolve(".", "serveflops_window_moe:"
                                 "window_moe_forward_flops")
    got = count(prompt=3, generated=2, vocab_size=11, hidden_size=8,
                hybrid_layer_pattern=[0, 1, 0], moe_layer_freq=[0, 1, 1],
                num_attention_heads=4, num_key_value_heads=2, head_dim=3,
                v_head_dim=2, swa_num_attention_heads=4,
                swa_num_key_value_heads=4, swa_head_dim=3, swa_v_head_dim=2,
                sliding_window=2, intermediate_size=16,
                moe_intermediate_size=5, n_routed_experts=8,
                num_experts_per_tok=2, held_experts=4)
    assert got == (2 * 1552 * 4 + 2 * 8 * 11 * 2
                   + 2 * (2 * 20 * 10 + 20 * 7)) == 13848


def test_the_cell_counts_the_issues_parameters(harness):
    """At the published widths a fed token's matrices are the arithmetic of
    the cut: global attention 89.13M, sliding 94.37M (the sinks are not
    products), the dense layer 201.33M, the router 1.05M, an expert 25.17M
    at 8 x 16 / 256 = half a token."""
    kwargs = harness.load_cell(
        CELL)["config_spec"]["serving"]["flops"]["kwargs"]
    count = harness.resolve(".", "serveflops_window_moe:"
                                 "window_moe_forward_flops")
    one = count(prompt=1, generated=1, **kwargs)   # one fed token, one pair
    two = count(prompt=2, generated=1, **kwargs)   # two fed, three pairs
    per_pair = 7 * 64 * (192 + 128)
    per_token = two - one - 2 * 2 * per_pair
    expert = 25_165_824
    want = (2 * 89_128_960 + 5 * 94_371_840 + 201_326_592
            + 6 * (1_048_576 + expert // 2))
    assert per_token == 2 * want
    assert one == 2 * want + 2 * per_pair + 2 * 4096 * 19072


def _edge(read, live, pairs, causal, **others):
    return {READ: read, LIVE: live, PAIRS: pairs, CAUSAL: causal,
            "serving_tokens_total": 1.0, **others}


# By hand: 1,280 positions read of 64,000 live; 4.1M pairs of 10M causal.
MARKS = {
    "both_edges": {"open": _edge(1000.0, 20000.0, 1e6, 2e6),
                   "close": _edge(2280.0, 84000.0, 5.1e6, 12e6)},
    "first_touched_inside_the_window": {
        "open": {"serving_tokens_total": 0.0},
        "close": _edge(1280.0, 64000.0, 4.1e6, 10e6)},
    "one_edge_missing": {"close": _edge(1280.0, 64000.0, 4.1e6, 10e6)},
    "no_marks": None,
    # the parent of the PR that brought the counters, or another block
    "a_program_without_the_counters": {
        "open": {"serving_tokens_total": 5.0},
        "close": {"serving_tokens_total": 9.0}},
    "nothing_inside_the_window": {
        "open": _edge(7.0, 9.0, 3.0, 4.0), "close": _edge(7.0, 9.0, 3.0, 4.0)},
}
WANT = {"sliding_kv_read_share": 2.0, "prefill_attn_pairs_share": 41.0}


@pytest.mark.parametrize("case", sorted(MARKS))
@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_of_the_window_counters(harness, metric, case):
    """Found the way a run finds it, by the metric's file; None wherever
    there is nothing to read, and never an error."""
    reader = harness.resolve("readers", harness.metric_spec(metric)["reader"])
    got = reader({"marks": MARKS[case]})
    read = case in ("both_edges", "first_touched_inside_the_window")
    assert got == (pytest.approx(WANT[metric]) if read else None)


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
        "moe_tiles_per_expert", "sliding_kv_read_share",
        "prefill_attn_pairs_share"}
    # appended behind every entry before them (later PRs append behind)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("sliding_kv_read_share")
    assert names[at:at + 2] == ["sliding_kv_read_share",
                                "prefill_attn_pairs_share"]
    assert at == names.index("feed_wait_ms") + 1
    for metric in manifest["per_layer"][at:at + 2]:
        assert metric["workloads"] == [CELL]
        assert (metric["unit"], metric["better"], metric["source"],
                metric["moves"]) == ("%", "lower", "program_counter",
                                     "serve_tokens_per_s")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "mimo_v2_5", "serve_closed_longdoc")
    assert manifest["workloads"].index(cell) == 5  # appended behind five
    assert [c["name"] for c in manifest["configs"]].index("mimo_v2_5") == 4


def test_the_configuration_is_the_published_one_cut_as_it_says(harness):
    """Every entry of the catalog row's ``config`` at the file's top level
    under the source's own key; the five cut keys as run, the published
    value beside; what the program is built with says the same, the router
    keeps its 256 outputs and the pattern is one whole period behind the
    dense layer."""
    spec = harness.load_cell(CELL)["config_spec"]
    pattern = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
    cut = {"num_hidden_layers": 7, "n_routed_experts": 16,
           "vocab_size": 19072, "hybrid_layer_pattern": pattern[:7],
           "moe_layer_freq": [0] + [1] * 6}
    assert spec["reduced"] == list(cut)
    assert spec["source_config"] == {
        "num_hidden_layers": 48, "n_routed_experts": 256,
        "vocab_size": 152576, "hybrid_layer_pattern": pattern,
        "moe_layer_freq": [0] + [1] * 47}
    for key, value in cut.items():
        assert spec[key] == value, key
    assert (spec["swa_num_key_value_heads"], spec["num_key_value_heads"],
            spec["head_dim"], spec["v_head_dim"], spec["sliding_window"],
            spec["swa_rope_theta"], spec["rope_theta"]) == (
        8, 4, 192, 128, 128, 10000, 10000000)
    assert spec["deployment"]["chips_sharing_a_layer"] == 16
    assert spec["deployment"]["chips_in_all"] == 112
    assert set(spec["assumed"]) >= {"layer_kinds", "rotary", "window",
                                    "sinks", "routing", "weights",
                                    "sinks_draw", "expert_bias", "max_len"}
    built = spec["model"]["kwargs"]
    assert built["n_routed_experts"] == 256  # the router keeps its width
    assert built["held_experts"] == [0, spec["n_routed_experts"]]
    assert (built["vocab_size"], built["max_len"]) == (19072, 13312)
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "v_head_dim",
                "swa_num_attention_heads", "swa_num_key_value_heads",
                "swa_head_dim", "swa_v_head_dim", "hybrid_layer_pattern",
                "moe_layer_freq", "sliding_window", "partial_rotary_factor",
                "rope_theta", "swa_rope_theta", "add_full_attention_sink_bias",
                "add_swa_attention_sink_bias", "attention_value_scale",
                "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "routed_scaling_factor",
                "layernorm_epsilon", "vocab_size"):
        assert built[key] == spec[key], key
    flops = spec["serving"]["flops"]["kwargs"]
    assert flops["held_experts"] == spec["n_routed_experts"]
    assert all(flops[k] == built[k] for k in flops if k != "held_experts")
    traffic = harness.load_cell(CELL)["traffic_spec"]
    engine = traffic["engine_kwargs"]
    assert engine["pages_per_slot"] * engine["page_size"] == built["max_len"]
    assert traffic["clients"] == engine["num_slots"] * 3 // 2
    assert traffic["prompt_tokens"]["high"] + traffic["output_tokens"][
        "high"] <= built["max_len"]


# ----------------------------------------------- the cell's files, driven tiny

RULES = {"requests": 6, "tokens_at_least": 20, "gap_widest_at_most": 0.05,
         "gap_fourth_mean_at_most": 1e-10, "control": "float8_e4m3fn"}


@pytest.fixture
def tiny_cell(harness):
    cell = copy.deepcopy(harness.load_cell(CELL))
    model = dict(TINY, vocab_size=512, held_experts=[0, 4])
    cell["config_spec"]["model"]["kwargs"] = model
    serving = cell["config_spec"]["serving"]
    serving["flops"]["kwargs"] = dict(
        {k: v for k, v in model.items() if k in serving["flops"]["kwargs"]},
        held_experts=4)
    # the weights come in bfloat16 and the program rounds a product's
    # operands to them, which the float32 reference does not: a near tie
    # falls the other way now and then, so the rehearsal's limits stand well
    # over nought (the chip's, at the cell's own size, are in the
    # configuration file)
    serving["correct"] = dict(RULES, gap_widest_at_most=2.0,
                              gap_fourth_mean_at_most=0.05)
    cell["traffic_spec"].update(
        clients=6, requests_per_client=40, ramp_s=0.5,
        prompt_tokens={"law": "uniform", "low": 4, "high": 40},
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
            "moe_held_share", "moe_tiles_per_expert",
            "state_bytes_per_position", "sliding_kv_read_share",
            "prefill_attn_pairs_share"} <= set(traced)
    assert traced["serve_compiles_in_window"]["value"] == 0
    # two global layers of 2 x 24 keys and 2 x 16 values, float32
    assert traced["state_bytes_per_position"]["value"] == 2 * 80 * 4
    # contexts of 5-47 against a window of 8; chunks within one block
    assert 10 < traced["sliding_kv_read_share"]["value"] < 100
    assert 100 < traced["prefill_attn_pairs_share"]["value"] < 200
    assert 0 < traced["serve_mfu"]["value"] < 100


def test_the_8bit_control_comes_out_as_not_correct(harness):
    """The control, both operands of every product rounded to
    ``float8_e4m3fn``, goes through the run's own ``judge`` and comes out as
    not correct; the reference's own greedy tokens come out as correct."""
    import servechecks

    reference = harness.load_module(".", "reference_window_moe")
    sizes = dict(TINY, hidden_size=128, held_experts=[0, 8])
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
