"""``JambaLM`` through ``ServingEngine``: a block whose Mamba layers keep a
**recurrent** state a slot (a float32 scan state and the convolution's last
inputs) beside the attention layers' pages, served by the same loop and the
same programs' builders.  At a small size on the CPU in float32, prefill and
then decoding agree with the plain reference's full-context pass at every
generated position (logits by the block's own ``DecodeSpec`` functions over
the engine's stores; the engine's served tokens by the reference's gaps):
for prompts that are not a bucket's length (whose padding must not reach the
state), shorter than the convolution's tail, in a slot that held another
request before, and for two slots at different lengths in one step; a
variant whose padding leaks into the state fails the same comparison; the
stores' types and sizes, the block's counter, and the builds the engine
refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import JambaLM
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.serving import GenerateRequest, ServingEngine
from distkeras_tpu.serving.cache import (PagedKVCache, fit_rows,
                                         set_slot_state)
from distkeras_tpu.telemetry.metrics import Registry

from test_jamba import TINY
from test_jamba import reference  # noqa: F401 (fixture)

LAYERS = TINY["num_hidden_layers"]
MAMBA = 3
PAGE, PAGES, WIDTH = 4, 16, 16   # a chunk of 16: whole pages, prompts shorter


@pytest.fixture(scope="module")
def lm():
    model = JambaLM(**TINY)
    return model, model.init(jax.random.PRNGKey(11))


@dataclasses.dataclass(frozen=True, eq=False)
class LeakyJambaLM(JambaLM):
    """The padding of a prefill chunk reaches the state: ``Delta`` is not
    zeroed past the prompt."""

    def mamba_chunk(self, p, h, live):
        return super().mamba_chunk(p, h, jnp.ones_like(live))


def _prompt(seed, length):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], length).tolist()


class Stores:
    """The engine's stores for two slots, driven by the block's own
    ``prefill`` and ``step`` as the engine's programs drive them: the pages
    through tables in reverse order, the recurrent kinds through
    ``set_slot_state``."""

    def __init__(self, spec):
        self.spec = spec
        cache = PagedKVCache(num_layers=LAYERS, num_slots=2, page_size=PAGE,
                             pages_per_slot=PAGES, state=spec.state,
                             layer_kinds=spec.layer_kinds,
                             recurrent=spec.recurrent, dtypes=spec.dtypes)
        self.pools = {n: list(layers) for n, layers in cache.pools.items()}
        pages = np.arange(2 * PAGES, 0, -1, dtype=np.int32)
        self.tables = jnp.asarray(pages.reshape(2, PAGES))
        self.pos = [0, 0]

    def prefill(self, params, slot, prompt):
        spec, plen = self.spec, len(prompt)
        recurrent = dict(spec.recurrent)
        chunk = jnp.zeros((1, WIDTH), jnp.int32).at[0, :plen].set(
            jnp.asarray(prompt))
        positions = jnp.arange(WIDTH)[None]
        table = self.tables[slot, :WIDTH // PAGE]
        x = spec.embed(params, chunk, positions)
        for li in range(LAYERS):
            def write(name, rows, li=li):
                pool = self.pools[name][li]
                if name in recurrent:
                    self.pools[name][li] = set_slot_state(pool, slot, rows)
                else:
                    self.pools[name][li] = pool.at[table].set(fit_rows(
                        rows.reshape(WIDTH // PAGE, PAGE, -1), pool))
            x, _ = spec.prefill(params, li, x, positions, write,
                                positions < plen)
        self.pos[slot] = plen
        return spec.head(params, x, at=plen - 1)

    def step(self, params, tokens, live):
        """One step of both slots: ``tokens`` a token a slot, ``live`` which
        take part; the logits ``[2, vocab]``."""
        spec = self.spec
        pos = jnp.asarray(self.pos, jnp.int32)
        x = spec.embed(params, jnp.asarray(tokens)[:, None], pos[:, None])
        for li in range(LAYERS):
            kinds = spec.kinds(li)
            layer, x, _ = spec.step(
                params, li, x, {n: self.pools[n][li] for n in kinds},
                self.tables, pos, jnp.asarray(live)[:, None])
            for name in kinds:
                self.pools[name][li] = layer[name]
        self.pos = [p + bool(a) for p, a in zip(self.pos, live)]
        return spec.head(params, x)[:, 0]


def _decode_against_the_reference(model, params, reference, plen, steps=8,
                                  slot=1):
    """The largest distance of the prompt's last row and of ``steps``
    teacher-forced steps in ``slot`` from the reference's logits."""
    stores = Stores(model.decode_spec(params))
    sequence = _prompt(plen, plen + steps)
    want = reference._reference_logits(params, jnp.asarray(sequence))
    worst = float(jnp.abs(stores.prefill(params, slot, sequence[:plen])
                          - want[plen - 1]).max())
    for at in range(plen, plen + steps):
        tokens = [0, 0]
        tokens[slot] = sequence[at]
        live = [i == slot for i in range(2)]
        got = stores.step(params, tokens, live)[slot]
        worst = max(worst, float(jnp.abs(got - want[at]).max()))
    return worst


@pytest.mark.parametrize("plen", [1, 2, 3, 5, 13], ids=lambda n: f"prompt_{n}")
def test_prefill_then_decode_equals_the_reference_logits(lm, reference, plen):
    """Prompts of 1 and 2 tokens (shorter than the convolution's tail of 3:
    its rows before position 0 are zeros), 3, and 5 and 13 in a chunk of 16
    (no bucket's length: 11 and 3 rows of padding), then 8 steps across
    page boundaries.  Tolerance float32's (logits of order 4)."""
    model, params = lm
    assert _decode_against_the_reference(model, params, reference,
                                         plen) < 3e-5


def test_padding_that_reaches_the_state_fails_the_comparison(lm, reference):
    """The same comparison with a block that lets the chunk's padding run
    through the scan: the state it hands over is not the prompt's, and the
    first steps' logits lie far off."""
    _, params = lm
    assert _decode_against_the_reference(
        LeakyJambaLM(**TINY), params, reference, 5) > 1e-2


def test_a_reused_slot_and_two_lengths_in_one_step(lm, reference):
    """Slot 1 serves a request of 11 tokens for 6 steps, then a request of 4
    takes it over; slot 0 runs a request of 7 meanwhile, so that every step
    holds two slots at different positions.  Every logit of both is the
    reference's: nothing of the slot's first request survives in its
    state."""
    model, params = lm
    stores = Stores(model.decode_spec(params))
    first, other, second = _prompt(1, 17), _prompt(2, 20), _prompt(3, 12)
    want = {name: reference._reference_logits(params, jnp.asarray(seq))
            for name, seq in (("first", first), ("other", other),
                              ("second", second))}
    stores.prefill(params, 1, first[:11])
    stores.prefill(params, 0, other[:7])
    worst = 0.0
    for k in range(6):
        got = stores.step(params, [other[7 + k], first[11 + k]], [True, True])
        worst = max(worst, float(jnp.abs(got[0] - want["other"][7 + k]).max()),
                    float(jnp.abs(got[1] - want["first"][11 + k]).max()))
    head = stores.prefill(params, 1, second[:4])
    worst = max(worst, float(jnp.abs(head - want["second"][3]).max()))
    for k in range(8):
        got = stores.step(params, [other[13 + k] if k < 7 else 0,
                                   second[4 + k]], [k < 7, True])
        if k < 7:
            worst = max(worst, float(jnp.abs(
                got[0] - want["other"][13 + k]).max()))
        worst = max(worst, float(jnp.abs(got[1] - want["second"][4 + k]).max()))
    assert worst < 3e-5
    # the scratch slot took no step's state, and the stores keep their types
    assert stores.pools["ssm"][0].dtype == jnp.float32
    assert stores.pools["ssm"][0].shape == (3, 16, 128)
    assert stores.pools["conv"][0].shape == (3, 3, 128)
    assert float(jnp.abs(stores.pools["ssm"][0][2]).max()) == 0.0


def test_the_engine_serves_it_and_counts_what_it_scans(lm, reference):
    """Five requests on two slots, prompts of 2 to 29 tokens on pages of 4:
    slots are given back and taken mid-flight.  The served token is the
    full-context reference's best everywhere; the scanned positions are the
    prefills' widths over the 3 Mamba layers; the stores' bytes are the
    pages' a position and the recurrent states' a slot, each in its type,
    under bfloat16 pools too."""
    model, params = lm
    registry = Registry()
    engine = ServingEngine(model, params, num_slots=2, page_size=4,
                           registry=registry)
    mix = [(29, 12), (6, 9), (2, 14), (13, 6), (8, 10)]
    try:
        ssm = engine._cache.pools["ssm"]
        assert [s is None for s in ssm] == [False, False, True, False]
        assert engine._cache.pools["k"][2].shape == (2 * 16 + 1, 4, 16)
        pending = [engine.submit(GenerateRequest(
            prompt=_prompt(plen, plen), max_new_tokens=new, temperature=0.0))
            for plen, new in mix]
        results = [p.result(timeout=600) for p in pending]
        assert engine._decode._cache_size() == 1  # one program, never retraced
        widths = [next(w for w in engine.prefill_buckets if w >= plen)
                  for plen, _ in mix]
        stats = engine.stats()
    finally:
        engine.stop()
    for (plen, new), result in zip(mix, results):
        assert result.finish_reason == "length" and len(result.tokens) == new
        gaps = reference.served_gaps(params, _prompt(plen, plen),
                                     result.tokens, TINY["max_len"])
        assert float(gaps.max()) <= 1e-5, (plen, new, gaps)
    snap = registry.snapshot()
    assert snap["serving_ssm_scan_positions_total"]["value"] == (
        MAMBA * sum(widths))
    # a position: one attention layer's key and value of 16, float32; a
    # slot: three Mamba layers' 16 x 128 float32 state and 3 x 128 tail
    per_slot = MAMBA * (16 * 128 * 4 + 3 * 128 * 4)
    assert snap["serving_state_per_position_bytes"]["value"] == 2 * 16 * 4
    assert snap["serving_state_per_slot_bytes"]["value"] == per_slot
    assert (stats["state_per_slot_recurrent_bytes"],
            stats["state_per_slot_sliding_bytes"]) == (per_slot, 0.0)
    low = ServingEngine(model, params, num_slots=2, page_size=4,
                        registry=Registry(), dtype="bfloat16")
    try:
        assert low._cache.pools["ssm"][0].dtype == jnp.float32
        assert low._cache.pools["conv"][0].dtype == jnp.bfloat16
        assert low._cache.pools["k"][2].dtype == jnp.bfloat16
        assert low.stats()["state_per_slot_bytes"] == MAMBA * (
            16 * 128 * 4 + 3 * 128 * 2)
        assert len(low.generate(_prompt(4, 9), max_new_tokens=5,
                                timeout=600).tokens) == 5
    finally:
        low.stop()


def test_a_hot_swap_keeps_the_layout(lm):
    model, params = lm
    engine = ServingEngine(model, params, num_slots=2, page_size=4,
                           registry=Registry())
    try:
        before = engine.generate(_prompt(1, 11), max_new_tokens=5,
                                 timeout=600)
        engine.hot_swap(model, model.init(jax.random.PRNGKey(12)), timeout=60)
        after = engine.generate(_prompt(1, 11), max_new_tokens=5,
                                timeout=600)
        assert before.tokens != after.tokens
        wider = JambaLM(**dict(TINY, mamba_d_state=8))
        with pytest.raises(ValueError, match="geometry"):
            engine.hot_swap(wider, wider.init(jax.random.PRNGKey(0)))
    finally:
        engine.stop()


@pytest.mark.parametrize("build", ["mesh", "draft_model"])
def test_the_engine_refuses_the_builds_the_block_does_not_bring(lm, build):
    """At construction, with an error that names the missing twin and the
    argument."""
    model, params = lm
    if build == "mesh":
        kwargs = {"mesh": make_mesh(2, axis_name="model")}
        twin = "JambaLM's block has no tensor-parallel twin"
    else:
        kwargs = {"draft_model": model, "draft_params": params}
        twin = "JambaLM's block has no speculative-verify twin"
    with pytest.raises(ValueError, match=build + "=") as raised:
        ServingEngine(model, params, num_slots=2, page_size=4,
                      registry=Registry(), **kwargs)
    assert twin in str(raised.value)


def test_a_draft_with_recurrent_state_is_refused(lm):
    """A block with recurrent state cannot be a draft: its cache-only
    prefill has no slot to hand the state to."""
    from distkeras_tpu.models import TransformerLM

    model, params = lm
    target = TransformerLM(vocab_size=TINY["vocab_size"], dim=32, heads=2,
                           num_layers=1, max_len=64)
    tparams = target.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="recurrent"):
        ServingEngine(target, tparams, num_slots=2, page_size=4,
                      registry=Registry(), draft_model=model,
                      draft_params=params)
