"""CPU rehearsal of ``chip_smoke.py``: the script's own phase functions at tiny
widths on the CPU mesh, so a wrong path, argument or check is found here and
not on the chip.  The LM path is steered onto the Pallas kernel (interpret
mode) from the test — the program has no option for that and needs none: on
the chip ``parallel.ring.attention`` picks the kernel by itself.

What only the chip can show (the kernel compiled, full widths, the device
line) is ``python chip_smoke.py`` on the chip; that the kernel compiles for
the chip at all is ``tests/test_chip_compile.py``.
"""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_TRAIN = dict(chip_smoke.TRAIN, batch=8, window=2, windows=4, epochs=2,
                  shape=(8, 8, 3))
TINY_MODEL = dict(vocab_size=97, dim=32, heads=2, num_layers=2, max_len=64)
TINY_LM = dict(chip_smoke.LM, seq=32, batch=2)
TINY_SERVE = dict(num_slots=4, prompt_len=12, new_tokens=6)


@pytest.fixture
def kernel_route(monkeypatch):
    """Route the models' attention call to the flash kernel, as on the chip;
    off the chip the kernel then picks the Pallas interpreter by itself."""
    from distkeras_tpu.models import transformer
    from distkeras_tpu.parallel import ring

    monkeypatch.setattr(transformer, "attention",
                        functools.partial(ring.attention, use_flash=True))


def test_train_phase():
    out = chip_smoke.phase_train(TINY_TRAIN)
    assert out["one_worker"]["commits"] == 1 * 4 * 2
    assert out["four_virtual_workers"]["commits"] == 4 * 1 * 1
    assert out["one_worker"]["loss"][-1] < out["one_worker"]["loss"][0]


def test_lm_kernel_agrees_with_reference():
    # a length the kernel has to pad, like the chip-compile cases
    errors = chip_smoke.attention_agreement((2, 48, 2, 16), jnp.bfloat16)
    assert set(errors) == {"out", "dq", "dk", "dv"}
    assert max(errors.values()) <= chip_smoke.ATTN_TOL


def test_lm_training_through_the_kernel(kernel_route):
    out = chip_smoke.lm_train(TINY_MODEL, TINY_LM)
    assert out["one_worker"]["commits"] == 2
    assert out["two_virtual_workers"]["commits"] == 2
    assert out["tokens_per_job"] == 2 * 2 * 2 * 32


def test_serve_phase():
    out = chip_smoke.phase_serve(TINY_MODEL, TINY_SERVE)
    assert out["requests"] == 4 and out["tokens"] == 4 * 6
    # f32 on the CPU: the paged engine and greedy_generate agree exactly
    assert out["equal_to_greedy_generate"] == 4 and out["near_ties"] == []


def test_serve_phase_refuses_a_real_difference(monkeypatch):
    """The tie rule tolerates a near-tie, nothing else: a reference that
    decodes different tokens fails the phase."""
    from distkeras_tpu import models

    def wrong(model, prompt, steps):
        out = real(model, prompt, steps).copy()
        out[:, -steps:] = (out[:, -steps:] + 1) % TINY_MODEL["vocab_size"]
        return out

    real = models.greedy_generate
    monkeypatch.setattr(models, "greedy_generate", wrong)
    with pytest.raises(chip_smoke.SmokeFailure, match="differs from greedy"):
        chip_smoke.phase_serve(TINY_MODEL, TINY_SERVE)


def test_four_chip_phase_on_virtual_devices():
    out = chip_smoke.phase_four_chips(TINY_TRAIN)
    assert out["worker_leaf_devices"] == {
        "no mesh": 4, "mesh of 2": 2, "mesh of 1": 1}
    apart = out["distance_over_movement"]
    # on the CPU the trainer's epoch and the engine-level one are the same
    # arithmetic to the bit: the rows reach both in the same order
    assert apart["f32_trainer_4x1_vs_1x4"] == 0.0
    assert apart["f32_trainer_vs_engine_without_mesh"] == 0.0
    assert out["unbounded_reading"]["bf16_trainer_4x1_vs_1x4"] == 0.0
    # 2 x 2 sums the four deltas in another order
    assert apart["bf16_2x2_vs_1x4"] < 1e-6


def test_exits_nonzero_and_prints_no_ok_line_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_compile_cache_is_placed_from_outside_or_at_a_fixed_path(monkeypatch):
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
        assert enable_compile_cache(REPO) == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_dir == was  # code set none
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        # the script says where its checkout is; the library does not guess
        fixed = os.path.join(REPO, ".jax_cache")
        from_examples = os.path.join(REPO, "examples", "..")
        assert enable_compile_cache(from_examples) == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
