"""The main path's Pallas kernels must compile for the chip they run on.

The CPU suite runs the flash-attention kernel in interpret mode only, which
checks its arithmetic but none of what the TPU compiler refuses (unaligned
slices, too much VMEM, a kernel that cannot be batched).  The TPU compiler is
installed without a chip attached, so these tests ask it directly: each case
lowers the kernel for a *described* ``v5e:2x2`` device at a shape the LM path
really uses and checks that a ``tpu_custom_call`` is in the compiled program.
Nothing runs — results are ``chip_smoke.py``'s business.

The topology is described inside a module-scoped fixture (never at import):
only one process may hold the TPU library, and under ``pytest -n`` every
worker imports this file but only one runs it.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distkeras_tpu.ops.pallas import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel(q, k, v):
    # interpret=False: the compiled kernel, whatever backend the test
    # process itself sits on; the blocks are the defaults, what callers run
    return flash_attention(q, k, v, True, interpret=False)


def _loss(q, k, v):
    return _kernel(q, k, v).astype(jnp.float32).sum()


def _compiled_text(fn, shape, dtype, sharding):
    arg = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return jax.jit(fn).lower(arg, arg, arg).compile().as_text()


# [batch, seq, heads, head_dim]: the benchmark's GPT-2 cell, GPT-2 small's
# attention at chip_smoke's LM batch in both dtypes, a 128-wide head, and two
# lengths the kernel must pad
@pytest.mark.parametrize("shape,dtype", [
    ((16, 1024, 12, 64), jnp.bfloat16),
    ((8, 1024, 12, 64), jnp.bfloat16),
    ((8, 1024, 12, 64), jnp.float32),
    ((2, 1024, 8, 128), jnp.bfloat16),
    ((2, 100, 12, 64), jnp.bfloat16),
    ((2, 48, 4, 32), jnp.float32),
])
def test_flash_attention_compiles_for_v5e(one_chip, shape, dtype):
    fwd = _compiled_text(_kernel, shape, dtype, one_chip)
    assert "tpu_custom_call" in fwd
    bwd = _compiled_text(jax.grad(_loss, argnums=(0, 1, 2)), shape, dtype,
                         one_chip)
    # forward (recomputed residuals) + the dq and dk/dv kernels
    assert bwd.count("tpu_custom_call") >= 3


# two virtual workers at chip_smoke's batch; the benchmark's cell, one worker
@pytest.mark.parametrize("shape", [(2, 8, 1024, 12, 64),
                                   (1, 16, 1024, 12, 64)])
def test_flash_attention_compiles_under_vmap_for_v5e(one_chip, shape):
    """Virtual workers put the kernel under ``vmap`` (a leading worker axis)
    inside the engine's shard_map and scan."""
    fwd = _compiled_text(jax.vmap(_kernel), shape, jnp.bfloat16, one_chip)
    assert "tpu_custom_call" in fwd
    bwd = _compiled_text(jax.vmap(jax.grad(_loss, argnums=(0, 1, 2))), shape,
                         jnp.bfloat16, one_chip)
    assert bwd.count("tpu_custom_call") >= 3


# ------------------------------------------------- the latent pool's layout


def test_the_latent_step_takes_its_pool_as_it_lies(one_chip):
    """The served latent-attention cell's step of one layer at its real
    shapes (64 slots, 192 pages of 16 a slot, 64 heads, a 576-wide row):
    the pool goes in and out in the row-major layout that the chip gives it,
    and the program holds no copy of a whole pool.  A pool 576 wide is laid
    out by the chip with the pages on the lanes, and every program re-laid
    it out on its way in and out (2.3 GB of copies a step: PERF.md, PR 31);
    ``pool_width`` pads a row to whole lanes, which this pins."""
    import re

    from distkeras_tpu.serving.cache import paged_latent_attention, pool_width

    slots, pages, page, heads, declared = 64, 192, 16, 64, 576
    width = pool_width(declared)
    assert width == 640
    pool_shape = (slots * pages + 1, page, width)
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def step(pool, tables, pos, q, row):
        return paged_latent_attention(pool, tables, pos, q, row, 512, 0.1)

    text = jax.jit(step, donate_argnums=(0,)).lower(
        struct(pool_shape, jnp.bfloat16), struct((slots, pages), jnp.int32),
        struct((slots,), jnp.int32), struct((slots, heads, declared), jnp.float32),
        struct((slots, declared), jnp.bfloat16)).compile().as_text()
    shape = "bf16[%d,%d,%d]" % pool_shape
    layouts = set(re.findall(re.escape(shape) + r"\{([\d,]+)", text))
    assert layouts == {"2,1,0"}, layouts  # row-major, everywhere it appears
    assert not [line for line in text.splitlines()
                if " copy(" in line and shape in line]
    assert "while" in text  # the loop over the live blocks


# ------------------------------------------- the expert layer's walk of tiles


def test_the_step_reads_each_experts_weights_where_they_lie(one_chip):
    """The served step's expert layer at the published widths (32 held
    experts of 4096 x 2048, 64 slots x 8 = 512 assignment rows, bfloat16):
    the walk over row tiles compiles for the chip as one loop, with no
    grouped product of XLA's own, and takes a tile's expert out of the
    stacked weights inside the products: the program holds no temporary as
    large as one expert's matrix (16.8 MB; a slice that copied the weights
    would triple the traffic that the walk removes: PERF.md, PR 32)."""
    from distkeras_tpu.models import LatentMoELM
    from distkeras_tpu.models.latent_moe import tile_height

    model = LatentMoELM(vocab_size=65536, max_len=3072, num_hidden_layers=5,
                        held_experts=(0, 32))
    slots, k, dim, width = 64, model.num_experts_per_tok, 4096, 2048
    assert tile_height(slots * k, model.num_experts) == 16
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    weights = {"experts_gate": struct((32, dim, width), jnp.bfloat16),
               "experts_up": struct((32, dim, width), jnp.bfloat16),
               "experts_down": struct((32, width, dim), jnp.bfloat16)}
    compiled = jax.jit(model.held_experts_terms).lower(
        weights, struct((slots, dim), jnp.float32),
        struct((slots, k), jnp.int32), struct((slots, k), jnp.float32),
        struct((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "ragged" not in text and text.count(" while(") == 1
    one_matrix = dim * width * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_matrix // 2


# ------------------------------------- two latent pools a layer, one step


def test_a_double_layers_step_copies_neither_pool_nor_the_experts(one_chip):
    """The shortcut-expert cell's step of one double layer at its real
    shapes (128 slots, 128 pages of 16 a slot, 64 heads, two 576-wide pools,
    16 held experts of 6144 x 2048 and a 768-output router, bfloat16): two
    ``paged_latent_attention`` calls against two pools inside one ``step``.
    Both pools go in and out row-major as they lie, the program holds no
    copy of either nor of the stacked expert weights or of a dense
    feed-forward's matrix, and its temporaries stay far under one pool."""
    import re

    from distkeras_tpu.models import ShortcutMoELM
    from distkeras_tpu.models.latent_moe import tile_height
    from distkeras_tpu.serving.cache import pool_width

    slots, pages, page = 128, 128, 16
    model = ShortcutMoELM(vocab_size=16384, max_len=pages * page,
                          num_layers=1, held_experts=(0, 16))
    assert tile_height(slots * model.moe_topk, model.router_width) == 16
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: struct(a.shape, a.dtype),
        jax.eval_shape(lambda key: model.init(key, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    spec = model.decode_spec(None)
    assert spec.state == (("latent_0", 576), ("latent_1", 576))
    pool_shape = (slots * pages + 1, page, pool_width(576))

    def step(params, first, second, x, tables, pos, live):
        pools, x, counts = spec.step(
            params, 0, x, {"latent_0": first, "latent_1": second}, tables,
            pos, live)
        return pools["latent_0"], pools["latent_1"], x, counts

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, struct(pool_shape, jnp.bfloat16),
        struct(pool_shape, jnp.bfloat16),
        struct((slots, 1, 6144), jnp.float32),
        struct((slots, pages), jnp.int32), struct((slots,), jnp.int32),
        struct((slots, 1), jnp.bool_)).compile()
    text = compiled.as_text()
    pool = "bf16[%d,%d,%d]" % pool_shape
    layouts = set(re.findall(re.escape(pool) + r"\{([\d,]+)", text))
    assert layouts == {"2,1,0"}, layouts  # row-major, everywhere it appears
    copied = [line for line in text.splitlines() if " copy(" in line and any(
        shape in line for shape in (
            pool, "bf16[16,6144,2048]", "bf16[16,2048,6144]",
            "bf16[6144,12288]", "bf16[12288,6144]"))]
    assert not copied, copied[:3]
    # the two pools' loops over live blocks and the walk over row tiles
    assert text.count(" while(") == 3 and "ragged" not in text
    one_pool = pool_shape[0] * pool_shape[1] * pool_shape[2] * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_pool // 4


# ------------------------------------------- the sampling's choice of a path


def test_the_sampling_keeps_its_sort_inside_a_branch(one_chip):
    """``sample_tokens`` at the latent cell's step shape (64 slots x 65,536
    float32 logits): the chip's compiler keeps the choice of a path as one
    ``conditional`` (it does not flatten it to a select that would run every
    path), and the sort over the vocabulary with its cumulative sum stands
    in a branch: the entry computation, which every step runs, holds
    neither (the sort was 13-16% of two serving cells' device time while
    every request was greedy: PERF.md, PR 34)."""
    import re

    from distkeras_tpu.serving.sampling import sample_tokens

    slots, vocab = 64, 65536
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    text = jax.jit(sample_tokens).lower(
        struct((slots, vocab), jnp.float32), struct((slots, 2), jnp.uint32),
        struct((slots,), jnp.float32), struct((slots,), jnp.int32),
        struct((slots,), jnp.float32), struct((slots,), jnp.bool_)
    ).compile().as_text()
    # computations by name; the entry is the one every call runs
    bodies = dict(re.findall(
        r"^(?:ENTRY )?(%[\w.\-]+) \(.*?\) -> .*? \{\n(.*?)^\}", text,
        re.M | re.S))
    (entry,) = re.findall(r"^ENTRY (%[\w.\-]+)", text, re.M)
    ops = lambda body: re.findall(r" ([\w\-]+)\(", body)
    assert ops(bodies[entry]).count("conditional") == 1
    assert not {"sort", "reduce-window"} & set(ops(bodies[entry]))
    (branches,) = re.findall(r"branch_computations=\{([^}]*)\}", text)
    greedy, plain, sorted_ = [bodies[name.strip()]
                              for name in branches.split(",")]
    assert "sort" in ops(sorted_) and "reduce-window" in ops(sorted_)
    assert not {"sort", "reduce-window"} & set(ops(greedy) + ops(plain))
