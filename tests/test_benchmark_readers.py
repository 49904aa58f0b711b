"""The benchmark's readers of the device trace, the host's clock and the
engine's counters (``benchmark/readers/device.py``, ``flash.py``, ``host.py``,
``serving.py``) on hand-made ``facts`` whose answers are worked out by hand,
each found the way a run finds it: by its metric's file.  And
``harness.peaks_for``, the one table of peaks that MFU and the roofline share."""

import json

import pytest

NS = 1e-9
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# One training job as ``drivers/train_job.py`` hands it to the readers.
#
# The trace, as ``tracelib.reduce_planes`` reduces one: 2 whole epochs in a
# slice of 2.0 s, 1.5 s of them busy, 6 programs begun; the three Pallas calls
# took 0.2 + 0.06 + 0.04 = 0.3 s of the device's own time (a fusion that
# bears the module's name is not the kernel).
#   dispatches_per_epoch 6 / 2 = 3; idle 1 - 1.5 / 2.0 = 25 %;
#   flash_time_share 0.3 / 1.5 = 20 %
# The roofline: batch 8, seq 1024, 12 heads of 64: 96 x 524,800 pairs x 64 x
# 2 = 6,448,742,400 operations a matmul; forward 2 of them, 12,897,484,800 /
# 197e12 = 65.47 us (bytes 50,724,864 / 819e9 = 61.94 us: compute-bound);
# backward 4, 130.94 us (bytes 101,449,728 / 819e9 = 123.87 us:
# compute-bound).  Calls: 2 epochs x 3 windows x 4 steps x 2 layers = 48.
#   flash_roofline 48 x 38,692,454,400 / 197e12 = 9.4276 ms of 0.3 s
# The watcher: epochs done at 0, 1, 2, (3 never seen alone), 4 and 5.5 s:
# intervals 1, 1, 1, 1 (the two that share 2..4) and 1.5 s; the 90th
# percentile is 0.6 of the way from the fourth to the fifth, 1.3 s.
# Compiles: 0.1 + 0.2 + 3.0 + 0.5 + 0.25 + 0.01 = 4.06 s over the job; the
# backend compiled at 0.9 (before the window), at 1.0 (its opening edge: not
# inside), at 2.5 and at 5.5 (its closing edge: inside); the trace at 3.0 is
# no backend compile.  compiles_in_window 2.
# MFU: 1e9 operations a row x 19,700 rows/s/chip over 197e12 = 10 %.
TRAIN = {
    "cell": {"config_spec": {
        "attention": {"trace_name": "^custom-call _SelfAttention_"},
        "flops": {"kwargs": {"seq": 1024, "heads": 12, "dim": 768,
                             "num_layers": 2}},
        "training": {"windows_per_worker_per_epoch": 3,
                     "trainer_kwargs": {"batch_size": 8,
                                        "communication_window": 4}}}},
    "trace": {"epochs": 2, "window_s": 2.0, "busy_s": 1.5, "between": None,
              "modules_per_epoch": 3.0,
              "named_s": {"custom-call _SelfAttention_0.5": 0.2,
                          "custom-call _SelfAttention_0.7": 0.06,
                          "custom-call _SelfAttention_0.9": 0.04,
                          "fusion _SelfAttention_0.2": 0.5,
                          "fusion fusion.1": 0.7}},
    "peaks": V5E, "traced_epoch": 3,
    "epoch_done": [0.0, 1.0, 2.0, None, 4.0, 5.5], "window": (1.0, 5.5),
    "compile_backend": "backend_compile",
    "compile_events": [(0.5, "trace", 0.1), (0.6, "lower", 0.2),
                       (0.9, "backend_compile", 3.0),
                       (1.0, "backend_compile", 0.5), (3.0, "trace", 0.0),
                       (2.5, "backend_compile", 0.25),
                       (5.5, "backend_compile", 0.01)],
    "flops_per_item": 1e9, "throughput": 19_700.0,
    "memory_peak_bytes": 12_345_678_901,
}

# One serving run as ``drivers/serve_open_loop.py`` hands it over: the
# registry's instruments at the window's two edges (a histogram is (sum,
# count)), ``engine.stats()`` three times inside it, the driver's own counts.
#   decode_step_ms (5.0 - 2.0) s / (250 - 100) steps = 20 ms
#   prefill_ms (1.85 - 1.0) s / (110 - 10) prefills = 8.5 ms
#   slot_occupancy mean(18, 24, 12) = 18 of 24 slots = 75 %
#   prefill_padding_share 3,000 padded / (3,000 + 9,000 prompt) = 25 %
#   goodput_share 380 tokens/s x 5 s = 1,900 of the engine's 2,000 = 95 %
#   serve_mfu 2e8 operations a token x 98,500 tokens/s over 197e12 = 10 %
#   the serving trace: 40 decode steps in 0.8 s, 0.48 s busy: idle 40 %
#   one backend compile inside (10, 15]: at 12.0
#   decode_argmax_share: of the window's 150 steps 30 sampled: 80 % took the
#   argmax alone
SERVE = {
    "cell": {"config_spec": {}},
    "window": (10.0, 15.0),
    "marks": {
        "open": {"serving_token_latency_seconds": (2.0, 100),
                 "serving_prefill_seconds": (1.0, 10),
                 "serving_prefill_padded_tokens": 1000.0,
                 "serving_tokens_total": 500.0,
                 "serving_decode_steps_total": 100.0,
                 "serving_decode_steps_sampled_total": 10.0},
        "close": {"serving_token_latency_seconds": (5.0, 250),
                  "serving_prefill_seconds": (1.85, 110),
                  "serving_prefill_padded_tokens": 4000.0,
                  "serving_tokens_total": 2500.0,
                  "serving_decode_steps_total": 250.0,
                  "serving_decode_steps_sampled_total": 40.0}},
    "samples": [(10.05, {"active_slots": 18, "slots_total": 24, "queue_depth": 3}),
                (10.10, {"active_slots": 24, "slots_total": 24, "queue_depth": 9}),
                (10.15, {"active_slots": 12, "slots_total": 24, "queue_depth": 0})],
    "prefilled_prompt_tokens": 9000,
    "summary": {"generated_per_s": 380.0, "ttft_p90_ms": 77.0},
    "trace": {"epochs": 40, "window_s": 0.8, "busy_s": 0.48, "between": None,
              "modules_per_epoch": 1.0, "named_s": {}},
    "peaks": V5E, "flops_per_item": 2e8, "throughput": 98_500.0,
    "compile_backend": "backend_compile",
    "compile_events": [(4.0, "backend_compile", 30.0),
                       (12.0, "backend_compile", 0.2), (12.5, "lower", 0.1)],
    "memory_peak_bytes": 9_876_543_210,
}

# metric -> (the facts it reads, its value by hand)
ANSWERS = {
    "dispatches_per_epoch": (TRAIN, 3.0),
    "device_idle_share": (TRAIN, 25.0),
    "flash_time_share": (TRAIN, 20.0),
    "flash_roofline": (TRAIN, 100.0 * 48 * 38_692_454_400 / 197e12 / 0.3),
    "epoch_ms_p90": (TRAIN, 1300.0),
    "compile_s": (TRAIN, 4.06),
    "compiles_in_window": (TRAIN, 2),
    "mfu": (TRAIN, 10.0),
    "peak_hbm_gb": (TRAIN, 12.345678901),
    "decode_step_ms": (SERVE, 20.0),
    "prefill_ms": (SERVE, 8.5),
    "slot_occupancy": (SERVE, 75.0),
    "prefill_padding_share": (SERVE, 25.0),
    "goodput_share": (SERVE, 95.0),
    "decode_argmax_share": (SERVE, 80.0),
    # the serving cell's entries of the same readers
    "serve_compiles_in_window": (SERVE, 1),
    "serve_device_idle_share": (SERVE, 40.0),
    "serve_mfu": (SERVE, 10.0),
    "serve_peak_hbm_gb": (SERVE, 9.87654321),
}

# The same runs with nothing to read: no device plane in the capture (a CPU,
# a capture that failed), a job of one epoch, no compile, a device that
# reports no memory, an engine whose edges were never read.  A count of
# nothing is a reading (0); everything else is left out.
NO_TRAIN = dict(TRAIN, trace=None, epoch_done=[3.0], window=(3.0, 3.0),
                compile_events=[], throughput=0.0, memory_peak_bytes=0)
NO_SERVE = dict(SERVE, trace=None, marks={}, samples=[], summary=None,
                prefilled_prompt_tokens=0, compile_events=[], throughput=0.0,
                memory_peak_bytes=0)
READ_AS_ZERO = {"compile_s", "compiles_in_window", "mfu",
                "serve_compiles_in_window", "serve_mfu"}


def _reader(harness, name):
    return harness.resolve("readers", harness.metric_spec(name)["reader"])


def _line(harness, name, facts):
    """The traced run's last line with this one per-layer metric, in a cell
    that it applies to, as ``run.py`` makes it."""
    manifest = harness.load_manifest()
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    cell = {"name": entry.get("workloads", ["cifar_cnn.downpour_1chip"])[0]}
    run = {"correct": True, "attempted": 5, "failed": 0, "facts": facts,
           "end_to_end": {}, "device": {"platform": "tpu"}}
    line = harness.result_line(dict(manifest, per_layer=[entry]), cell, run, True)
    json.dumps(line)
    return entry, line


def test_every_reader_without_a_test_of_its_own_is_here(harness):
    """Each per-layer metric of the manifest is read here, on the hand-made
    ring (``test_benchmark_span_readers.py``) or on the hand-made marks
    (``test_benchmark_decode_kv_reader.py``,
    ``test_benchmark_decode_chained_reader.py``,
    ``test_benchmark_mla_moe.py``, ``test_benchmark_scmoe.py``,
    ``test_benchmark_serve_loop_reader.py``)."""
    elsewhere = {"gather_ms", "h2d_ms", "dispatch_ms", "host_slack_ms",
                 "feed_gap_ms", "gap_unattributed_share", "decode_kv_read_share",
                 "decode_chained_share", "moe_held_share",
                 "moe_load_max_over_mean", "state_bytes_per_position",
                 "moe_tiles_per_expert", "moe_zero_share",
                 "moe_real_picks_max_over_mean", "loop_host_ms",
                 "loop_wait_share", "step_dispatch_ms", "emit_ms",
                 "queue_wait_ms", "dispatch_starved_share", "gc_pause_share"}
    names = {m["name"] for m in harness.load_manifest()["per_layer"]}
    assert names == set(ANSWERS) | elsewhere


@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_reader_on_hand_made_facts(harness, capsys, name):
    facts, want = ANSWERS[name]
    entry, line = _line(harness, name, dict(facts))
    assert line["metrics"] == {name: {"value": pytest.approx(want, rel=1e-12),
                                      "unit": entry["unit"]}}
    notes = [json.loads(text) for text in capsys.readouterr().out.splitlines()]
    if name != "flash_roofline":
        assert notes == []
        return
    # the roofline says on a line of its own which bound applies to each pass
    (note,) = notes
    assert note["flash_roofline_bound"] == {"forward": "compute",
                                            "backward": "compute"}
    assert note["flash_calls_per_pass"] == 48
    assert note["flash_device_s"] == pytest.approx(0.3)
    assert note["flash_least_s"] == pytest.approx(48 * 38_692_454_400 / 197e12)


@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_reader_finds_nothing_on_facts_without_its_source(harness, capsys, name):
    facts = NO_TRAIN if ANSWERS[name][0] is TRAIN else NO_SERVE
    _, line = _line(harness, name, dict(facts))
    if name in READ_AS_ZERO:
        assert line["metrics"][name]["value"] == 0
    else:
        # left out of the line, and the line is made all the same
        assert line["metrics"] == {}
    assert line["correct"] is True and line["attempted"] == 5
    assert capsys.readouterr().out == ""


def test_the_flash_readers_need_the_configuration_to_name_the_kernel(harness):
    """A configuration without an ``attention`` group (the CNN), and a trace
    in which no operation bears the kernel's name."""
    plain = dict(TRAIN, cell={"config_spec": {}})
    unnamed = dict(TRAIN, trace=dict(TRAIN["trace"],
                                     named_s={"fusion fusion.1": 1.5}))
    for name in ("flash_time_share", "flash_roofline"):
        assert _reader(harness, name)(plain) is None
        assert _reader(harness, name)(unnamed) is None
    # the roofline counts calls by whole epochs: none from a short capture
    short = dict(TRAIN, trace=dict(TRAIN["trace"], epochs=0))
    assert _reader(harness, "flash_roofline")(short) is None
    assert _reader(harness, "flash_time_share")(short) == pytest.approx(20.0)


def test_the_roofline_takes_the_bound_that_binds(harness, capsys):
    """With memory a thousand times slower both passes are memory-bound:
    48 x (50,724,864 + 101,449,728) bytes / 819e6 = 8.9187 s of 0.3 s."""
    slow = dict(TRAIN, peaks=dict(V5E, hbm_bytes_per_s=819e6))
    assert _reader(harness, "flash_roofline")(slow) == pytest.approx(
        100.0 * 48 * 152_174_592 / 819e6 / 0.3, rel=1e-12)
    assert json.loads(capsys.readouterr().out)["flash_roofline_bound"] == {
        "forward": "memory", "backward": "memory"}


# A capture that holds only the boundary between two epochs (a cell's
# ``capture_s``; ``test_benchmark_tracelib.py`` reduces one such): 185 ns of
# which 127 busy, the device idle for 48 of the 50 ns between the two epoch
# programs, 2 programs begun there.  The programs' own idle rate is (185 - 127
# - 48) / (185 - 50) = 10 / 135; over the traced epoch's period by the
# watcher, 1000 ns, the idle time is 48 + 950 x 10 / 135 = 118.37 ns.
EDGE = dict(TRAIN, traced_epoch=3,
            epoch_done=[0.0, 1.0, 2.0, 3.0, 3.0 + 1000 * NS],
            trace={"epochs": 0, "window_s": 185 * NS, "busy_s": 127 * NS,
                   "modules_per_epoch": 2,
                   "between": {"seconds": 50 * NS, "idle_s": 48 * NS,
                               "modules": 2}})


def test_device_readers_on_a_boundary_capture(harness):
    idle = _reader(harness, "device_idle_share")
    assert idle(EDGE) == pytest.approx(100 * (48 + 950 * 10 / 135) / 1000)
    assert _reader(harness, "dispatches_per_epoch")(EDGE) == 2
    # the watcher never saw the traced epoch's other end
    assert idle(dict(EDGE, traced_epoch=4)) is None
    assert idle(dict(EDGE, epoch_done=[0.0, 1.0, 2.0, 3.0, None])) is None
    # a capture inside one epoch program: no boundary, no reading
    inside = dict(EDGE, trace=dict(EDGE["trace"], between=None,
                                   modules_per_epoch=None))
    assert idle(inside) is None
    assert _reader(harness, "dispatches_per_epoch")(inside) is None


def test_epoch_ms_p90_of_one_interval_is_that_interval(harness):
    p90 = _reader(harness, "epoch_ms_p90")
    assert p90({"epoch_done": [2.0, 2.25]}) == pytest.approx(250.0)
    # two epochs seen at once share their interval
    assert p90({"epoch_done": [0.0, None, 3.0]}) == pytest.approx(1500.0)
    # an epoch that the count never reached adds nothing
    assert p90({"epoch_done": [0.0, 1.0, None]}) == pytest.approx(1000.0)


def test_a_histogram_that_did_not_move_gives_no_mean(harness):
    """Both edges read, no decode step between them: nothing to divide by."""
    still = dict(SERVE, marks={"open": SERVE["marks"]["open"],
                               "close": SERVE["marks"]["open"]})
    for name in ("decode_step_ms", "prefill_ms", "goodput_share"):
        assert _reader(harness, name)(still) is None
    one_edge = dict(SERVE, marks={"close": SERVE["marks"]["close"]})
    for name in ("decode_step_ms", "prefill_ms", "prefill_padding_share",
                 "goodput_share"):
        assert _reader(harness, name)(one_edge) is None


def _steps(steps, sampled=None, **others):
    edge = {"serving_decode_steps_total": steps, **others}
    if sampled is not None:
        edge["serving_decode_steps_sampled_total"] = sampled
    return edge


# decode_argmax_share on hand-made marks: 820 steps inside the window, 205
# of them with a sampling slot: 75 % took the argmax alone.
SAMPLING_PATHS = {
    "both_counters": ({"open": _steps(100.0, 5.0),
                       "close": _steps(920.0, 210.0)}, 75.0),
    "every_step_greedy": ({"open": _steps(10.0, 0.0),
                           "close": _steps(50.0, 0.0)}, 100.0),
    "every_step_sampled": ({"open": _steps(10.0, 10.0),
                            "close": _steps(50.0, 50.0)}, 0.0),
    # first touched inside the window: it stood at nought at the opening edge
    "counter_missing_at_the_opening_edge": (
        {"open": _steps(100.0), "close": _steps(300.0, 50.0)}, 75.0),
    # the parent of the PR that brought the counter sorts on every step and
    # counts none of it: nothing, not 100 %
    "the_counter_absent": ({"open": _steps(100.0, serving_tokens_total=5.0),
                            "close": _steps(920.0, serving_tokens_total=9.0)},
                           None),
    "no_steps_inside_the_window": ({"open": _steps(9.0, 2.0),
                                    "close": _steps(9.0, 2.0)}, None),
    "one_edge_missing": ({"close": _steps(820.0, 205.0)}, None),
    "no_marks": (None, None),
}


@pytest.mark.parametrize("case", sorted(SAMPLING_PATHS))
def test_decode_argmax_share_on_hand_made_marks(harness, case):
    marks, want = SAMPLING_PATHS[case]
    entry, line = _line(harness, "decode_argmax_share", {"marks": marks})
    assert entry["workloads"][:3] == [
        "gpt2_small.serve_prefill_heavy", "sarvam_105b.serve_closed_decode",
        "longcat_flash_omni.serve_closed_reasoning"]
    if want is None:
        # left out, and the line is made all the same (the parent's side)
        assert line["metrics"] == {}
    else:
        assert line["metrics"] == {"decode_argmax_share": {
            "value": pytest.approx(want, rel=1e-12), "unit": "%"}}
    assert line["correct"] is True and line["attempted"] == 5


def test_the_counters_the_sampling_reader_reads_are_the_engines():
    from distkeras_tpu.serving import serving_metrics
    from distkeras_tpu.telemetry.metrics import Registry

    registry = Registry()
    serving_metrics(registry)
    assert {"serving_decode_steps_total", "serving_decode_steps_sampled_total",
            "serving_decode_steps_sorted_total"} <= set(registry.snapshot())


def test_peaks_for_the_v5e_as_jax_names_it(harness):
    entry = harness.peaks_for("TPU v5 lite")
    assert entry["name"] == "TPU v5e"
    assert entry["bf16_flops_per_s"] == 197e12
    assert entry["hbm_bytes_per_s"] == 819e9
    assert harness.peaks_for("TPU v5e") is not None
    assert {k: entry[k] for k in V5E} == V5E


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 imagined", ""])
def test_peaks_for_an_unknown_device_is_an_error_naming_the_file(harness, kind):
    """No peak is ever guessed: a device that the table lacks stops the run
    and says where to add it."""
    with pytest.raises(SystemExit) as refused:
        harness.peaks_for(kind)
    assert "benchmark/peaks.json" in str(refused.value)
    assert repr(kind) in str(refused.value)
