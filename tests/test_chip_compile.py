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
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


# ------------------------- sliding-window and global layers, pages and rings


def _window_cut(one_chip, pattern, moe_layer_freq):
    """The sliding-window expert cell's block cut to the layers of
    ``pattern`` at the published widths (16 held experts, 19,072 rows of the
    vocabulary), with its weights' shapes on the described chip."""
    from distkeras_tpu.models import WindowMoELM

    model = WindowMoELM(vocab_size=19072, max_len=13312,
                        num_hidden_layers=len(pattern),
                        hybrid_layer_pattern=pattern,
                        moe_layer_freq=moe_layer_freq, held_experts=(0, 16))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda key: model.init(key, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    return model, params


def test_a_global_and_a_sliding_layers_step_copies_no_store(one_chip):
    """The sliding-window cell's step through a global and a sliding expert
    layer at its real shapes (32 slots, 832 pages of 16 a slot, 4 and 8
    key-value heads of 192 and 128, a ring of 128 rows a slot, 16 held
    experts of 4096 x 2048, bfloat16): the grouped-query read of the pages
    and the ring's read with the sinks in one program.  No page pool, ring
    or stacked expert weights are copied, and the temporaries stay far
    under one page pool."""
    import re

    from distkeras_tpu.serving.cache import pool_width

    model, params = _window_cut(one_chip, [0, 1], [1, 1])
    spec = model.decode_spec(None)
    assert spec.state == (("k", 768), ("v", 512), ("sliding_k", 1536),
                          ("sliding_v", 1024))
    slots, pages, page = 32, 832, 16
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    shapes = {"k": (slots * pages + 1, page, 768),
              "v": (slots * pages + 1, page, 512),
              "sliding_k": (slots + 1, 128, pool_width(1536)),
              "sliding_v": (slots + 1, 128, pool_width(1024))}
    stores = {name: struct(shape, jnp.bfloat16)
              for name, shape in shapes.items()}

    def step(params, stores, x, tables, pos, live):
        stores = dict(stores)
        for li in range(2):
            kinds = spec.kinds(li)
            layer, x, _ = spec.step(params, li, x,
                                    {n: stores[n] for n in kinds}, tables,
                                    pos, live)
            stores.update(layer)
        return stores, x

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, stores, struct((slots, 1, 4096), jnp.float32),
        struct((slots, pages), jnp.int32), struct((slots,), jnp.int32),
        struct((slots, 1), jnp.bool_)).compile()
    text = compiled.as_text()
    big = ["bf16[%d,%d,%d]" % shape for shape in shapes.values()] + [
        "bf16[16,4096,2048]", "bf16[16,2048,4096]"]
    copied = [line for line in text.splitlines()
              if " copy(" in line and any(shape in line for shape in big)]
    assert not copied, copied[:3]
    for shape in big[:4]:  # row-major, everywhere a store appears
        assert set(re.findall(re.escape(shape) + r"\{([\d,]+)", text)) <= {
            "2,1,0"}, shape
    one_pool = 2 * (slots * pages + 1) * page * 768
    assert compiled.memory_analysis().temp_size_in_bytes < one_pool // 8


def test_a_12288_wide_prefill_holds_no_whole_score_matrix(one_chip):
    """A 12,288-wide prefill of the global dense layer at the published
    widths, the layer whose queries see the most keys: its blocks of 512
    queries, each projected, attended and carried out through ``W_o`` on its
    own, keep the temporaries under 2 GB (its dense float32 scores, ``[64,
    W, W]``, would be 38.7 GB).  (The whole seven-layer prefill compiles to
    3.4 GB: PERF.md, section 4.)"""
    model, params = _window_cut(one_chip, [0], [0])
    spec = model.decode_spec(None)
    width = 12288
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def prefill(params, x, live):
        rows = []
        x, _ = spec.prefill(params, 0, x, jnp.arange(width)[None],
                            lambda name, r: rows.append(r), live)
        return x, rows

    compiled = jax.jit(prefill).lower(
        params, struct((1, width, 4096), jnp.float32),
        struct((1, width), jnp.bool_)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 10**9


# ------------------------------------ the paged read: one kernel, pools in place


def _paged_step_text(fn, kpool, vpool, tables, pos, q, k, v):
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(
        kpool, vpool, tables, pos, q, k, v).compile()
    return compiled.as_text()


def _reads_the_pools_as_they_lie(text, pools, slots, widths):
    """One ``tpu_custom_call``, the kernel's; no gathered block of every
    slot (``[slots, 8 pages, 16, width]`` or ``[slots, 128, width]``); each
    pool row-major everywhere it appears and never copied."""
    import re

    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"%paged_decode_attention[.\d]* = ", text)
    for width in widths:
        for block in (f"[{slots},8,16,{width}]", f"[{slots},128,{width}]"):
            assert block not in text, block
    for pool in pools:
        assert set(re.findall(re.escape(pool) + r"\{([\d,]+)", text)) == {
            "2,1,0"}, pool
        assert not [line for line in text.splitlines()
                    if " copy(" in line and pool in line]


@pytest.mark.parametrize("slots,pages,widths,heads,head_dim,dtype", [
    # the GPT-2 cell: 24 slots of 64 pages, 12 heads of 64, float32 pools
    (24, 64, (768, 768), 12, 64, jnp.float32),
    # the MiMo cell's global layer: 32 slots of 832 pages, 4 key-value
    # heads of 192 / 128 serving 16 query heads each, bfloat16 pools
    (32, 832, (768, 512), 64, 192, jnp.bfloat16),
    # the Jamba cell's attention layer: 256 slots of 160 pages, one
    # key-value head of 128 serving 20 query heads (a group padded to 24)
    (256, 160, (128, 128), 20, 128, jnp.bfloat16),
])
def test_the_paged_read_is_one_kernel_over_the_pools_as_they_lie(
        one_chip, slots, pages, widths, heads, head_dim, dtype):
    """``paged_decode_attention`` lowered for the chip at the cells' shapes
    takes the Pallas kernel (the CPU takes the gathered read: the same
    function's lowering in ``test_serving_paged_attention.py`` holds its
    loop), and the kernel reads each pool where the step's row was written
    into it: in place, donated through the program (PERF.md)."""
    from distkeras_tpu.serving.cache import paged_decode_attention

    struct = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    kv_heads = widths[0] // head_dim
    v_dim = widths[1] // kv_heads
    shapes = [(slots * pages + 1, 16, w) for w in widths]
    text = _paged_step_text(
        paged_decode_attention, *(struct(s, dtype) for s in shapes),
        struct((slots, pages), jnp.int32), struct((slots,), jnp.int32),
        struct((slots, 1, heads, head_dim), jnp.float32),
        struct((slots, 1, kv_heads, head_dim), jnp.float32),
        struct((slots, 1, kv_heads, v_dim), jnp.float32))
    name = "f32" if dtype == jnp.float32 else "bf16"
    _reads_the_pools_as_they_lie(
        text, ["%s[%d,%d,%d]" % (name, *s) for s in set(shapes)], slots,
        widths)
    assert "input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}" in text
    assert " while(" not in text  # no loop of XLA's over blocks


def test_the_paged_read_is_one_kernel_under_the_tensor_parallel_mesh(topo):
    """As the engine's tensor-parallel build calls it: the GPT-2 cell's pools
    and heads split over a one-axis ``shard_map`` of 2 described chips
    (``check_vma``), each chip's kernel over its own 384-wide pools."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from distkeras_tpu.serving.cache import paged_decode_attention
    from distkeras_tpu.utils import compat

    mesh = Mesh(topo.devices[:2], ("model",))
    pool, heads = P(None, None, "model"), P(None, None, "model", None)
    fn = compat.shard_map(
        paged_decode_attention, mesh,
        in_specs=(pool, pool, P(), P(), heads, heads, heads),
        out_specs=(pool, pool, heads), check_vma=True)
    struct = lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, spec))
    rows = struct((24, 1, 12, 64), jnp.float32, heads)
    text = _paged_step_text(
        fn, struct((1537, 16, 768), jnp.float32, pool),
        struct((1537, 16, 768), jnp.float32, pool),
        struct((24, 64), jnp.int32, P()), struct((24,), jnp.int32, P()),
        rows, rows, rows)
    _reads_the_pools_as_they_lie(text, ["f32[1537,16,384]"], 24, (384,))



# --------------------------- the Mamba layers' selective scan: two kernels


def test_the_selective_scan_kernels_compile_at_the_cells_widths(one_chip):
    """``ssm_scan`` over the cell's widest prefill chunk (512 positions of
    5,120 channels, a state of 16) and ``ssm_step`` over its 256 slots of a
    store of 257 rows (the last the engine's scratch slot), lowered for the
    chip: each one kernel under its own name, the step's store written where
    it lies (aliased to the output, donated through the program, never
    copied)."""
    import re

    from distkeras_tpu.ops.pallas import ssm_scan, ssm_step

    struct = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    rows, width, n, slots = 512, 5120, 16, 256
    scan = jax.jit(ssm_scan).lower(
        struct((rows, width)), struct((rows, width)), struct((rows, n)),
        struct((rows, n)), struct((n, width)), struct((width,))
    ).compile().as_text()
    assert scan.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"%ssm_scan[.\d]* = ", scan)
    step = jax.jit(ssm_step, donate_argnums=0).lower(
        struct((slots + 1, n, width)), struct((slots, width)),
        struct((slots, width)), struct((slots, n)), struct((slots, n)),
        struct((n, width)), struct((width,)), struct((slots,), jnp.bool_)
    ).compile().as_text()
    assert step.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"%ssm_step[.\d]* = ", step)
    assert "input_output_alias={ {0}: (0, {}, may-alias)" in step
    store = "f32[%d,%d,%d]" % (slots + 1, n, width)
    assert not [line for line in step.splitlines()
                if " copy(" in line and store in line]
