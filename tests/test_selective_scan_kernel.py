"""The Pallas kernels of the Mamba layers' selective scan
(``ops.pallas.selective_scan``), run by the Pallas interpreter, against the
plain ``jax.numpy`` recurrence that the CPU runs and against a float64 loop
written here: ``ssm_scan`` over a prefill chunk cut into several blocks of
channels and of positions (the state carried from one block of positions to
the next, rows of ``Delta = 0`` leaving it as it was), ``ssm_step`` over
slots in several groups with some not live (their state as it was, the
store's scratch row untouched); and which shapes the compiled kernels are
chosen for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas import selective_scan as ss

N, WIDTH = 16, 256


def _inputs(rng, rows):
    """``x``, ``Delta`` (softplus of a shifted normal: steps of 0.01-1),
    ``B``, ``C`` of ``rows`` rows; Mamba's own ``A`` and ``D``."""
    x = rng.normal(size=(rows, WIDTH)).astype(np.float32)
    delta = np.log1p(np.exp(rng.normal(size=(rows, WIDTH)) - 2)).astype(
        np.float32)
    b, c = (rng.normal(size=(rows, N)).astype(np.float32) for _ in range(2))
    a = -np.tile(np.arange(1, N + 1, dtype=np.float32)[:, None], (1, WIDTH))
    d = rng.normal(size=WIDTH).astype(np.float32)
    return x, delta, b, c, a, d


def loop64(x, delta, b, c, a, d, h):
    """The recurrence in float64, a position at a time from state ``h``."""
    h = np.array(h, np.float64)
    ys = []
    for t in range(x.shape[0]):
        h = (np.exp(delta[t][None] * a) * h
             + b[t][:, None] * (delta[t] * x[t])[None])
        ys.append((c[t][:, None] * h).sum(0) + d * x[t])
    return np.array(ys), h


@pytest.fixture(scope="module")
def chunk():
    rng = np.random.default_rng(40)
    args = _inputs(rng, 48)
    # the rows past a prompt of 37: Delta zeroed, as the block zeroes it
    args = (args[0], np.where(np.arange(48)[:, None] < 37, args[1], 0.0)
            .astype(np.float32)) + args[2:]
    # two blocks of channels and three of positions
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ss, "SCAN_BLOCKS", (128, 16))
        got = ss.ssm_scan(*map(jnp.asarray, args),
                          interpret=pltpu.InterpretParams())
    plain = ss.plain_scan(*map(jnp.asarray, args))
    want = loop64(*args, np.zeros((N, WIDTH)))
    return args, [np.asarray(t) for t in got], [np.asarray(t) for t in plain], want


def test_the_scan_kernel_equals_the_plain_scan_and_float64(chunk):
    """Two blocks of channels and three of positions: the state crosses the
    blocks of positions in VMEM and comes out after the last."""
    _, (y, h), (y_plain, h_plain), (y64, h64) = chunk
    np.testing.assert_allclose(y, y_plain, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, h_plain, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, y64, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h, h64, rtol=1e-4, atol=1e-4)


def test_rows_of_no_step_leave_the_state_as_the_prompt_left_it(chunk):
    """The state handed over after 48 rows, 11 of them with ``Delta = 0``,
    is the state after the prompt's 37."""
    args, (_, h), _, _ = chunk
    _, after_prompt = loop64(*(t[:37] for t in args[:4]), *args[4:],
                             np.zeros((N, WIDTH)))
    np.testing.assert_allclose(h, after_prompt, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("slots", [16, 5],
                         ids=["two_groups_of_8", "one_group_of_5"])
def test_the_step_kernel_advances_the_live_slots_in_place(slots, monkeypatch):
    """One position of every slot from a drawn state: a live slot's state
    and output are the plain step's and float64's; a slot that is not live
    keeps its state; the store's scratch row is untouched."""
    rng = np.random.default_rng(slots)
    x, delta, b, c, a, d = _inputs(rng, slots)
    state = rng.normal(size=(slots + 1, N, WIDTH)).astype(np.float32)
    live = rng.random(slots) < 0.6
    live[0], live[-1] = True, False
    args = (state, x, delta, b, c, a, d, live)
    # groups of at most 8 slots and two blocks of channels; the generic
    # interpreter: the store's rows are not whole groups
    monkeypatch.setattr(ss, "STEP_BLOCKS", (8, 128))
    new, y = (np.asarray(t) for t in ss.ssm_step(
        *map(jnp.asarray, args), interpret=True))
    plain_new, plain_y = (np.asarray(t) for t in ss.plain_step(
        *map(jnp.asarray, args)))
    np.testing.assert_allclose(new, plain_new, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, plain_y, rtol=1e-5, atol=1e-5)
    for s in range(slots):
        y64, h64 = loop64(x[s:s + 1], delta[s:s + 1], b[s:s + 1],
                          c[s:s + 1], a, d, state[s])
        np.testing.assert_allclose(y[s], y64[0], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(new[s], h64 if live[s] else state[s],
                                   rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(new[slots], state[slots])


def _struct(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("x,a,takes", [
    ((512, 5120), (16, 5120), True),     # the cell's widest prefill
    ((128, 5120), (16, 5120), True),
    ((48, 256), (16, 256), True),
    ((12, 256), (16, 256), False),       # positions not whole sublane tiles
    ((48, 96), (16, 96), False),         # channels not whole lanes
])
def test_the_scan_kernel_is_chosen_from_the_shapes_alone(x, a, takes):
    assert ss.scan_fits(_struct(x), _struct(a)) is takes


@pytest.mark.parametrize("state,x,takes", [
    ((257, 16, 5120), (256, 5120), True),            # the cell
    ((4, 16, 256), (3, 256), True),                  # one group
    ((13, 16, 256), (12, 256), False),               # 12: not whole groups
    ((257, 16, 96), (256, 96), False),               # channels not lanes
])
def test_the_step_kernel_is_chosen_from_the_shapes_alone(state, x, takes):
    assert ss.step_fits(_struct(state), _struct(x)) is takes
    assert not ss.step_fits(_struct(state, jnp.bfloat16), _struct(x))
