"""Tests of the serving quarter of the benchmark, on the CPU at a tiny size.

    python -m pytest benchmark/test_serve.py -q        # or: selftest.py serve

Not a measurement: the tiny model proves the control flow, the arithmetic and
that ``correct`` fails where it must.  Kept here and not under ``tests/``:
the yardstick's tests live with the yardstick.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import contextlib
import copy
import io
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(HERE), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import reference_lm  # noqa: E402
import servechecks  # noqa: E402
import serveflops  # noqa: E402
import servegen  # noqa: E402

MODEL = {"vocab_size": 512, "dim": 64, "heads": 4, "num_layers": 2,
         "max_len": 128}
#: the float32 program on the CPU is exact, so at this size the limits stand
#: just over nought (the chip's, at the cell's own size, are in the
#: configuration file)
RULES = {"requests": 8, "tokens_at_least": 20, "gap_widest_at_most": 0.05,
         "gap_fourth_mean_at_most": 1e-12, "control": "bfloat16"}
TRAFFIC = {"open": dict(arrival="poisson", rate_per_s=20.0, knee_per_s=25.0,
                        rate_from="a test", ramp_s=0.5,
                        prompt_tokens={"law": "log_uniform", "low": 8, "high": 32},
                        output_tokens={"law": "log_uniform", "low": 4, "high": 16}),
           "closed": dict(arrival="closed", clients=6, requests_per_client=50,
                          ramp_s=0.5,
                          prompt_tokens={"law": "uniform", "low": 40, "high": 100},
                          output_tokens={"law": "uniform", "low": 2, "high": 8})}
#: both arrival kinds rehearse on the one serving cell's files
CELLS = {"open": "gpt2_small.serve_prefill_heavy",
         "closed": "gpt2_small.serve_prefill_heavy"}


def tiny_cell(which):
    """A serving cell's own files with the model and the sizes swapped for
    tiny ones (a tiny preset lives here, never in a cell)."""
    cell = copy.deepcopy(harness.load_cell(CELLS[which]))
    cell["config_spec"]["model"]["kwargs"] = dict(MODEL)
    serving = cell["config_spec"]["serving"]
    serving["flops"]["kwargs"] = {k: MODEL[k] for k in (
        "vocab_size", "dim", "heads", "num_layers")}
    serving["correct"] = dict(RULES)
    cell["traffic_spec"].update(TRAFFIC[which])
    cell["capture_s"] = 0.3
    cell["traffic_spec"]["engine_kwargs"] = {"num_slots": 4, "page_size": 8,
                                             "queue_size": 64}
    return cell


def drive(which, seed=3_000_000_019, seconds=1.5, trace=False):
    driver = harness.load_driver("serve_open_loop")
    device = {"platform": "cpu", "kind": "TPU v5 lite (described)", "count": 1}
    sink = io.StringIO()
    with contextlib.redirect_stdout(sink), contextlib.redirect_stderr(sink):
        run = driver.run(cell=tiny_cell(which), seed=seed, seconds=seconds,
                         trace=trace, t_start=time.perf_counter(),
                         device=device)
    return run, sink.getvalue()


# ---------------------------------------------------------------- schedule


def test_schedule_is_a_function_of_the_seed_alone():
    traffic = tiny_cell("open")["traffic_spec"]
    make = lambda seed: servegen.schedule(traffic, seed, 2.0, vocab=512,
                                          max_len=128)
    one, again, other = make(2**31 + 7), make(2**31 + 7), make(8)
    assert one == again
    assert one != other
    # another seed: the same lengths and the same gaps, in another order
    lengths = lambda plan: sorted((len(r["prompt"]), r["max_new"]) for r in plan)
    assert sorted(len(r["prompt"]) for r in one) == sorted(
        len(r["prompt"]) for r in other)
    assert sorted(r["max_new"] for r in one) == sorted(
        r["max_new"] for r in other)
    assert lengths(one) != lengths(other) or one[0]["prompt"] != other[0]["prompt"]
    # ramp 0.5 s and window 2 s at 20 a second: 10 and 40 arrivals, each
    # block with its own lengths and gaps, whatever the seed
    assert len(one) == len(other) == 50
    for plan in (one, other):
        assert all(r["due_s"] < 0.5 for r in plan[:10])
        assert all(0.5 < r["due_s"] < 2.5 for r in plan[10:])
    inside = lambda plan: sorted(len(r["prompt"]) for r in plan[10:])
    assert inside(one) == inside(other)
    assert ([r["due_s"] for r in one[10:]] != [r["due_s"] for r in other[10:]])
    assert all(0 <= t < 512 for r in one for t in r["prompt"])
    assert all(8 <= len(r["prompt"]) <= 32 and 4 <= r["max_new"] <= 16
               for r in one)


def test_closed_schedule_deals_the_requests_round():
    traffic = tiny_cell("closed")["traffic_spec"]
    plan = servegen.schedule(traffic, 5, 2.0, vocab=512, max_len=128)
    assert len(plan) == 300 and all(r["due_s"] is None for r in plan)
    assert [r["client"] for r in plan[:7]] == [0, 1, 2, 3, 4, 5, 0]
    # every round of six holds the same lengths, whatever the seed
    other = servegen.schedule(traffic, 6, 2.0, vocab=512, max_len=128)
    rounds = {tuple(sorted((len(r["prompt"]) for r in run[i:i + 6])))
              for run in (plan, other) for i in range(0, 300, 6)}
    assert len(rounds) == 1
    assert len({tuple(sorted(r["max_new"] for r in run[i:i + 6]))
                for run in (plan, other) for i in range(0, 300, 6)}) == 1
    assert [len(r["prompt"]) for r in plan[:6]] != [
        len(r["prompt"]) for r in other[:6]]


def test_quantile_lengths_and_gaps_do_not_depend_on_a_seed():
    law = {"law": "log_uniform", "low": 64, "high": 256}
    lengths = servegen.quantile_lengths(law, 4)
    # 64 x 4^(1/8), 4^(3/8), 4^(5/8), 4^(7/8)
    assert lengths.tolist() == [76, 108, 152, 215]
    uniform = servegen.quantile_lengths({"law": "uniform", "low": 0, "high": 8}, 4)
    assert uniform.tolist() == [1, 3, 5, 7]
    np.testing.assert_allclose(servegen.poisson_gaps(200, 40.0).sum(), 40.0)
    traffic = {"arrival": "poisson", "rate_per_s": 4.8, "ramp_s": 20}
    assert servegen.blocks(traffic, 20.0) == [(0.0, 20.0, 96), (20.0, 20.0, 96)]


# -------------------------------------------------------------- arithmetic


def record(due, sent, ttft_s, done, tokens, max_new=None, prompt=10, **more):
    base = {"due": due, "sent": sent, "ttft_s": ttft_s, "done": done,
            "tokens": tokens, "max_new": tokens if max_new is None else max_new,
            "prompt_tokens": prompt, "reason": "length", "error": None}
    base.update(more)
    return base


def test_latency_counts_from_the_due_time_when_the_sender_is_late():
    driver = harness.load_driver("serve_open_loop")
    # due at 10.0, sent half a second late, first token 0.1 s after the send,
    # 11 tokens, done at 11.6: ttft 0.6 s, tpot (1.6 - 0.6) / 10 = 0.1 s
    late = record(10.0, 10.5, 0.1, 11.6, 11)
    got = driver.summarise([late], 10.0, 30.0, 90.0)
    assert abs(got["ttft_p90_ms"] - 600.0) < 1e-6
    assert abs(got["tpot_p90_ms"] - 100.0) < 1e-6
    assert abs(got["latency_p90_ms"] - 1600.0) < 1e-6


def test_percentiles_tokens_and_failures_on_hand_made_records():
    driver = harness.load_driver("serve_open_loop")
    # ten requests due at 10 + i, sent on time, first token after (i + 1) x
    # 10 ms, 5 tokens each, done 1 s after they were due
    records = [record(10.0 + i, 10.0 + i, 0.01 * (i + 1), 11.0 + i, 5)
               for i in range(10)]
    # one before the window (counts for nothing), one refused, one that never
    # came back, one with a token missing
    records += [record(5.0, 5.0, 0.01, 6.0, 5),
                record(12.5, 12.5, None, None, 0, max_new=5, error="queue_full"),
                record(13.5, 13.5, None, None, 0, max_new=5),
                record(14.5, 14.5, 0.01, 15.5, 4, max_new=5)]
    got = driver.summarise(records, 10.0, 20.0, 80.0)
    assert got["attempted"] == 13 and got["failed"] == 3
    assert got["unanswered"] == 2  # refused is failed, not unanswered
    # finished inside [10, 20): the ten (done 11..20 -> nine of them, the
    # tenth is done at 20.0) and the short one: 9 x 5 + 4 tokens over 10 s
    assert got["completed_in_window"] == 10
    assert abs(got["tokens_per_s"] - 4.9) < 1e-9
    # made inside the window: all five tokens of each of the ten (the tenth
    # ends as the window closes) and the short one's four
    assert abs(got["generated_per_s"] - 5.4) < 1e-9
    # a request in flight as the window opens or closes counts for its share:
    # first token at 8.0, 11 tokens, done at 13.0; a window from 10 holds the
    # six that came after it, a window that closes at 10 the first five
    astride = [record(7.0, 7.0, 1.0, 13.0, 11)]
    assert abs(driver.summarise(astride, 10.0, 20.0, 80.0)["generated_per_s"]
               - 0.6) < 1e-9
    assert abs(driver.summarise(astride, 0.0, 10.0, 80.0)["generated_per_s"]
               - 0.5) < 1e-9
    assert abs(got["processed_per_s"] - (49 + 100) / 10.0) < 1e-9
    # p90 over the ten alone, as statistics.quantiles' inclusive method has it
    ten = driver.summarise(records[:10], 10.0, 20.0, 80.0)
    assert abs(ten["ttft_p90_ms"] - 91.0) < 1e-6   # 90 + 0.1 x (100 - 90)
    assert abs(ten["ttft_p50_ms"] - 55.0) < 1e-6
    # tpot of request i: (1 - 0.01 (i + 1)) / 4
    assert abs(ten["tpot_p50_ms"] - 1e3 * (1 - 0.055) / 4) < 1e-6
    # a request that was refused or never answered is in every tail with the
    # deadline's wait, the time a token too: dropping requests cannot read
    # as a better tail for those that are left
    assert got["latency_p90_ms"] > 50_000
    assert got["tpot_p90_ms"] > 50_000 and got["per_token_p90_ms"] > 50_000
    assert got["ttft_p90_ms"] > 50_000
    # the whole latency over the tokens: request i took 1 s for 5 tokens
    assert abs(ten["per_token_p90_ms"] - 200.0) < 1e-6


def test_forward_flops_by_hand():
    # one layer, d = 4, V = 10, prompt 3, generated 2: 4 tokens are fed;
    # blocks 12 x 16 = 192 parameters, 2 x 192 x 4 = 1536; head 2 x 4 x 10 x 2
    # = 160; attention 4 x 4 x (1 + 2 + 3 + 4) = 160
    got = serveflops.transformer_lm_forward_flops(
        vocab_size=10, dim=4, heads=2, num_layers=1, prompt=3, generated=2)
    assert got == 1536 + 160 + 160


# ----------------------------------------------------------------- correct


def reference_row(weights, tokens, width):
    """The reference's logits for the token after ``tokens`` (padded to one
    compiled width: the mask is causal)."""
    padded = np.zeros(width, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(reference_lm._reference_logits(weights, padded)[len(tokens) - 1])


def greedy(weights, prompt, steps, width=MODEL["max_len"]):
    tokens = list(prompt)
    for _ in range(steps):
        tokens.append(int(np.argmax(reference_row(weights, tokens, width))))
    return tokens[len(prompt):]


def test_correct_turns_false_when_one_served_token_is_swapped():
    weights = reference_lm.make_weights(11, **MODEL)
    prompt = list(range(3, 23))
    served = greedy(weights, prompt, 24)
    gaps = reference_lm.served_gaps(weights, prompt, served, MODEL["max_len"])
    assert gaps.shape == (24,) and float(gaps.max()) == 0.0
    assert not servechecks.judge(gaps, RULES)[1]
    # swap the tenth token for one whose reference logit lies 1.0 or more
    # below the best at that position
    row = reference_row(weights, prompt + served[:9], MODEL["max_len"])
    worse = int(np.flatnonzero(row <= row.max() - 1.0)[0])
    swapped = served[:9] + [worse] + served[10:]
    gaps = reference_lm.served_gaps(weights, prompt, swapped, MODEL["max_len"])
    assert gaps[9] >= 1.0
    compared, reasons = servechecks.judge(gaps, RULES)
    assert reasons and compared["gap_widest"]["value"] >= 1.0


def test_the_committed_limits_fail_a_gap_of_one_on_hand_made_gaps():
    """``judge`` with the configuration's own rules, no model: five thousand
    served tokens that are the reference's best pass; one of them a whole
    1.0 below the best fails, and says which number."""
    rules = harness.load_cell(CELLS["closed"])["config_spec"]["serving"]["correct"]
    gaps = np.zeros(5000, np.float32)
    compared, reasons = servechecks.judge(gaps, rules)
    assert not reasons and compared["tokens_compared"]["value"] == 5000
    gaps[500] = 1.0
    compared, reasons = servechecks.judge(gaps, rules)
    assert reasons and "gap_widest" in reasons[0]
    # and three tokens are too few to say anything
    assert servechecks.judge(np.zeros(3, np.float32), rules)[1]


def test_the_sample_is_seeded_and_holds_the_longest():
    finished = [{"index": i, "prompt_tokens": 10 + i % 7, "tokens": 5 + i % 3,
                 "served": [1] * (5 + i % 3)} for i in range(40)]
    one = servechecks.sample(finished, 9, 8)
    assert one == servechecks.sample(finished, 9, 8) and len(one) == 8
    assert one != servechecks.sample(finished, 10, 8)
    longest = max(r["prompt_tokens"] + r["tokens"] for r in finished)
    assert one[0]["prompt_tokens"] + one[0]["tokens"] == longest


def test_the_control_comes_out_as_not_correct():
    """The control, the reference in bfloat16 put in the program's place,
    goes through the run's own ``judge`` and comes out as not correct, on
    three seeds; the float32 program's tokens (on the CPU: the reference's
    own) come out as correct.  An 8-bit float reads far above bfloat16.  The
    chip's readings, at the cell's own size, stand in the configuration."""
    model = dict(MODEL, dim=128, num_layers=4, vocab_size=4096)
    rules = dict(RULES, tokens_at_least=200)
    for seed in (1, 2, 3):
        weights = reference_lm.make_weights(seed, **model)
        ours, lower, eighth = [], [], []
        for start in (5, 105, 205, 305):
            prompt = list(range(start, start + 40))
            served = greedy(weights, prompt, 80)
            got = reference_lm.served_gaps(
                weights, prompt, served, model["max_len"], "bfloat16")
            ours.append(got[0])
            lower.append(got[1])
            eighth.append(reference_lm.served_gaps(
                weights, prompt, served, model["max_len"], "float8_e4m3fn")[1])
        ours, lower, eighth = (np.concatenate(part)
                               for part in (ours, lower, eighth))
        assert not servechecks.judge(ours, rules)[1]
        compared, reasons = servechecks.judge(lower, rules)
        assert reasons, (seed, compared)
        assert (compared["gap_fourth_mean"]["value"]
                > 3 * rules["gap_fourth_mean_at_most"]), (seed, compared)
        assert (eighth ** 4).mean() > 3 * (lower ** 4).mean(), seed


# ------------------------------------------------------- the driver, whole

#: the serving cell's per-layer metrics, beside ``compile_s``
SERVING_LAYERS = {"decode_step_ms", "prefill_ms", "slot_occupancy",
                  "prefill_padding_share", "goodput_share", "serve_mfu",
                  "serve_compiles_in_window", "serve_device_idle_share",
                  "serve_peak_hbm_gb", "compile_s"}
#: no device plane in a trace of the CPU, and no memory statistics
ON_A_CHIP_ONLY = {"serve_device_idle_share", "serve_peak_hbm_gb"}


def test_open_loop_run_end_to_end():
    run, said = drive("open")
    assert run["correct"], said[-2000:]
    assert run["attempted"] > 10 and run["failed"] == 0
    assert set(run["end_to_end"]) == {"setup_s", "serve_tokens_per_s",
                                      "tpot_p90_ms"}
    assert all(v > 0 for v in run["end_to_end"].values())
    assert run["compared"]["gap_widest"]["value"] <= RULES["gap_widest_at_most"]
    assert "compared gap_widest:" in said.splitlines()[-3]
    manifest = harness.load_manifest()
    cell = tiny_cell("open")
    line = harness.result_line(manifest, cell, run, False)
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == set(run["end_to_end"])
    traced = harness.result_line(manifest, cell, run, True)["metrics"]
    assert SERVING_LAYERS - ON_A_CHIP_ONLY <= set(traced)
    # an open loop's own readers have something to read, for the cell that
    # a later PR adds with files alone
    serving = harness.load_module("readers", "serving")
    assert serving.queue_depth(run["facts"]) is not None
    assert serving.first_token_p90_ms(run["facts"]) > 0


def test_closed_loop_run_end_to_end():
    run, said = drive("closed", trace=True)
    assert run["correct"], said[-2000:]
    assert run["attempted"] > 20 and run["failed"] == 0
    line = harness.result_line(harness.load_manifest(), tiny_cell("closed"),
                               run, False)
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                    "tpot_p90_ms"}
    traced = harness.result_line(harness.load_manifest(), tiny_cell("closed"),
                                 run, True)["metrics"]
    assert SERVING_LAYERS - ON_A_CHIP_ONLY <= set(traced)
    assert traced["serve_compiles_in_window"]["value"] == 0
    assert "serve_device_idle_share" not in traced  # no device plane here
    assert not any(name in traced for name in ("epoch_ms_p90", "mfu"))


def test_a_token_altered_where_it_is_produced_fails_the_run():
    """The rest of a run with the timed path broken underneath: the decode
    step's sampling gives every slot the token after the one it chose."""
    from distkeras_tpu.serving import engine as engine_module

    sound = engine_module.sample_tokens

    def altered(logits, *rest):
        return (sound(logits, *rest) + 1) % logits.shape[-1]

    engine_module.sample_tokens = altered
    try:
        run, _ = drive("open", seed=77)
    finally:
        engine_module.sample_tokens = sound
    assert not run["correct"]
    assert run["compared"]["gap_widest"]["value"] > RULES["gap_widest_at_most"]
    assert run["failed"] == 0  # every answer came, on time: they are wrong


#: the tests that need no model and no engine: ``selftest.py files`` runs
#: them too, and the repo's suite runs that
WITHOUT_A_MODEL = (
    test_schedule_is_a_function_of_the_seed_alone,
    test_closed_schedule_deals_the_requests_round,
    test_quantile_lengths_and_gaps_do_not_depend_on_a_seed,
    test_latency_counts_from_the_due_time_when_the_sender_is_late,
    test_percentiles_tokens_and_failures_on_hand_made_records,
    test_forward_flops_by_hand,
    test_the_committed_limits_fail_a_gap_of_one_on_hand_made_gaps,
    test_the_sample_is_seeded_and_holds_the_longest)
