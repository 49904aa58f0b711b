"""Smoke run of distkeras_tpu on the chip: does the system still start there?

    python chip_smoke.py            # one chip: train, lm, serve
    python chip_smoke.py --chips 4  # four chips: the collective commit only

One process, public entry points, seeded data and weights, no network.  It
refuses to run on anything but a TPU (non-zero exit, no result line), never
picks another backend, and any failed check raises — nothing is caught to let
the run go on.  Every line it prints is one JSON object; the LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The seconds printed are smoke readings (one cold pass, compile included), not
benchmark numbers.

Phases (one chip):
  train  the paper's headline job at its real shape: CIFAR-10 CNN under
         DOWNPOUR, batch 256, window 16, bf16 — one worker for two epochs,
         then four virtual workers (vmap) for one.
  lm     GPT-2-small-width TransformerLM (12 x 768, vocab 50257, seq 1024)
         trained through DOWNPOUR with token cross-entropy; proves the Pallas
         flash kernel is what the attention call compiles to, alone and under
         vmap, and that it agrees with the jnp reference on this chip.
  serve  ServingEngine over the same width: four concurrent requests, each
         checked token by token against greedy_generate.

Four chips (``--chips 4``): the train job with ``num_workers=4`` through
``dk.DOWNPOUR(...).train(df)`` with four chips visible (the trainer's own pick
of devices, the commit a psum across chips) against the same epoch on one of
the chips as 4 virtual workers.  Checked: the same center variable in f32; in
bf16, the job's dtype, 2 chips x 2 workers against 1 chip x 4; worker state
really spread over the devices.  Only the sides that need a mesh of fewer
chips than are visible are built at engine level: the trainer takes no mesh.
"""

import argparse
import contextlib
import functools
import json
import math
import os
import sys
import time

import numpy as np

SEED = 0

#: the headline job — the paper's ``cifar_cnn_downpour`` (ROADMAP.md R12's
#: table): shapes are the configuration's own, only the number of
#: windows is smoke-sized.  The learning rate (the table's is 0.05) and the
#: data's scale of 0.5 were chosen so that the loss falls on this synthetic
#: data and the "loss falls" check means something; at 0.05 the CNN diverges
#: within an epoch, f32 as well as bf16 (measured on the chip).
TRAIN = dict(batch=256, window=16, windows=8, epochs=2, shape=(32, 32, 3),
             classes=10, optimizer=("sgd", {"learning_rate": 0.01,
                                            "momentum": 0.9}))
#: GPT-2 small's widths (the block models/hf_staged.py maps GPT-2 onto)
LM_MODEL = dict(vocab_size=50257, dim=768, heads=12, num_layers=12,
                max_len=1024)
LM = dict(seq=1024, batch=8, window=2, windows=2,
          optimizer=("sgd", {"learning_rate": 0.01}))
SERVE = dict(num_slots=4, prompt_len=128, new_tokens=32)

#: flash kernel vs jnp reference, both fed the same bf16 inputs, the
#: reference computed in f32: max|kernel - ref| <= ATTN_TOL * max|ref|.
#: bf16 keeps 8 mantissa bits (2^-8 = 0.4%); outputs and gradients are sums
#: of such roundings, so 2% of the result's scale is a bound a wrong block,
#: mask or scale cannot meet.
ATTN_TOL = 2e-2
#: a served token may differ from greedy_generate's only where the
#: reference's own top-2 logits are closer than this (logits of a seeded
#: model here have unit scale); the paged and the contiguous cache sum in
#: different orders, so an exact tie-break cannot be demanded
TIE_GAP = 5e-2
#: two layouts of the same 4-worker epoch: L2 distance of their center
#: variables over the L2 distance the center travelled from its start.  The
#: layouts are the same arithmetic in another order; measured on the chip
#: after two windows: 5.5e-3 (f32, 4 chips x 1 vs 1 chip x 4) and 3.8e-3
#: (bf16, 2 chips x 2 vs 1 chip x 4).  A worker's delta lost or counted
#: twice in the commit is 0.25 or more.
CENTER_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(ok, message):
    if not ok:
        raise SmokeFailure(message)


def emit(**fields):
    print(json.dumps(fields), flush=True)


@contextlib.contextmanager
def compile_clock():
    """Sum JAX's own trace/lower/compile durations while the block runs.
    ``compile_s`` covers the XLA compile or, on a persistent-cache hit, the
    retrieval — so a warm second run shows up as a smaller number."""
    import jax.monitoring as monitoring

    clock = {"trace_lower_s": 0.0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            clock["compile_s"] += seconds
        elif event.startswith("/jax/core/compile/"):
            clock["trace_lower_s"] += seconds

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            clock["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        yield clock
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)


def timed_phase(name, fn, *args):
    """Run one phase, print its line (wall split into compile and the rest),
    and let any failure propagate.  JAX's trace durations nest (a jit traced
    inside a jit is counted in both), so their sum can exceed the wall a
    little: ``run_s`` is what is left, never below zero."""
    t0 = time.perf_counter()
    with compile_clock() as clock:
        fields = fn(*args)
    wall = time.perf_counter() - t0
    compile_s = clock["compile_s"] + clock["trace_lower_s"]
    emit(phase=name, compile_s=round(compile_s, 2),
         xla_compile_s=round(clock["compile_s"], 2),
         cache_hits=clock["cache_hits"],
         run_s=round(max(0.0, wall - compile_s), 2), wall_s=round(wall, 2),
         **fields)


# ------------------------------------------------------------------- train


def cifar_like(n, shape, classes, seed):
    """CIFAR-shaped rows with a learnable rule: each class has a fixed random
    template, a row is its class's template plus noise of the same size."""
    rng = np.random.default_rng(seed)
    templates = rng.standard_normal(size=(classes,) + tuple(shape),
                                    dtype=np.float32)
    y = rng.integers(0, classes, size=n)
    x = rng.standard_normal(size=(n,) + tuple(shape), dtype=np.float32)
    x += templates[y]
    x *= 0.5
    return x, y


def worker_optimizer(cfg, num_workers):
    """DOWNPOUR adds every worker's delta to the center, so the worker
    learning rate is the configuration's divided by the worker count."""
    name, knobs = cfg["optimizer"]
    return name, dict(knobs,
                      learning_rate=knobs["learning_rate"] / num_workers)


def downpour_job(module, loss, cfg, df, rows, num_workers, epochs,
                 compute_dtype="bfloat16"):
    """``dk.DOWNPOUR(...).train(df)`` at ``cfg``'s batch and window; checks
    the losses and that the center counted every worker's commit of every
    window.  Returns the line's fields and the trained model."""
    import distkeras_tpu as dk
    from distkeras_tpu.models import FlaxModel

    trainer = dk.DOWNPOUR(
        FlaxModel(module), loss=loss,
        worker_optimizer=worker_optimizer(cfg, num_workers),
        metrics=(), num_workers=num_workers, batch_size=cfg["batch"],
        num_epoch=epochs, communication_window=cfg["window"],
        compute_dtype=compute_dtype, seed=SEED)
    model = trainer.train(df)
    losses = [float(v) for v in trainer.get_history()["loss"]]
    windows = rows // (num_workers * cfg["window"] * cfg["batch"])
    check(len(losses) == epochs, f"{epochs} epochs asked, history has {losses}")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss: {losses}")
    check(trainer.num_updates == num_workers * windows * epochs,
          f"center counted {trainer.num_updates} commits, expected "
          f"{num_workers} workers x {windows} windows x {epochs} epochs")
    return {"workers": num_workers, "windows_per_worker": windows,
            "epochs": epochs, "commits": trainer.num_updates, "loss": losses,
            "train_s": round(trainer.get_training_time(), 2)}, model


def phase_train(cfg=TRAIN):
    import distkeras_tpu as dk
    from distkeras_tpu.models import CIFARCNN

    rows = cfg["windows"] * cfg["window"] * cfg["batch"]
    x, y = cifar_like(rows, cfg["shape"], cfg["classes"], SEED)
    df = dk.from_numpy(x, np.eye(cfg["classes"], dtype=np.float32)[y])
    job = functools.partial(downpour_job, CIFARCNN(num_classes=cfg["classes"]),
                            "categorical_crossentropy", cfg, df, rows)
    one, _ = job(1, cfg["epochs"])
    check(one["loss"][-1] < one["loss"][0],
          f"loss did not fall over the epochs: {one['loss']}")
    four, _ = job(4, 1)
    return {"model": "CIFARCNN", "batch": cfg["batch"],
            "window": cfg["window"], "compute_dtype": "bfloat16",
            "one_worker": one, "four_virtual_workers": four}


# ---------------------------------------------------------------------- lm


def compiled_with_kernel(shape, dtype, workers=None):
    """Compile ``parallel.ring.attention`` — the call the models make — for
    this backend and say whether the Pallas kernel is in the program
    (``workers`` adds the engine's vmap over virtual workers)."""
    import jax

    from distkeras_tpu.parallel.ring import attention

    fn = lambda q, k, v: attention(q, k, v, causal=True)
    if workers:
        fn, shape = jax.vmap(fn), (workers,) + tuple(shape)
    arg = jax.ShapeDtypeStruct(shape, dtype)
    fwd = jax.jit(fn).lower(arg, arg, arg).compile().as_text()
    loss = lambda q, k, v: fn(q, k, v).astype("float32").sum()
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg, arg, arg).compile().as_text()
    return "tpu_custom_call" in fwd and "tpu_custom_call" in bwd


def attention_agreement(shape, dtype):
    """Forward and the three gradients of the flash kernel against
    ``local_attention`` in f32 on the same inputs; returns the worst
    normalised error of each."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.pallas import flash_attention
    from distkeras_tpu.parallel.ring import local_attention

    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                  for kk in keys)

    def outputs(attn, cast, q, k, v, g):
        def loss(q, k, v):
            return (attn(q, k, v).astype(jnp.float32)
                    * g.astype(jnp.float32)).sum()

        q, k, v = (a.astype(cast) for a in (q, k, v))
        return (attn(q, k, v),) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    flash = lambda q, k, v: flash_attention(q, k, v, True)
    reference = lambda q, k, v: local_attention(q, k, v, causal=True)
    got = jax.jit(functools.partial(outputs, flash, dtype))(q, k, v, g)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(
            functools.partial(outputs, reference, jnp.float32))(q, k, v, g)
    errors = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        check(np.isfinite(a).all(), f"flash attention {name} is not finite")
        errors[name] = float(np.abs(a - b).max() / np.abs(b).max())
    return errors


def lm_tokens(rows, seq, vocab, seed):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, size=(rows, seq + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def lm_train(model_cfg=LM_MODEL, cfg=LM):
    """One worker for ``windows`` windows, then two virtual workers for one
    window each on the same rows (the kernel under vmap inside the engine's
    shard_map and scan)."""
    import distkeras_tpu as dk
    from distkeras_tpu.models import TransformerLM

    rows = cfg["windows"] * cfg["window"] * cfg["batch"]
    x, y = lm_tokens(rows, cfg["seq"], model_cfg["vocab_size"], SEED)
    job = functools.partial(downpour_job, TransformerLM(**model_cfg),
                            "token_crossentropy", cfg, dk.from_numpy(x, y),
                            rows)
    one, _ = job(1, 1)
    uniform = math.log(model_cfg["vocab_size"])
    check(abs(one["loss"][0] - uniform) < 1.0,
          f"untrained LM loss {one['loss'][0]:.3f} is not near ln(vocab) = "
          f"{uniform:.3f}")
    two, _ = job(2, 1)
    return {"ln_vocab": round(uniform, 3), "tokens_per_job": rows * cfg["seq"],
            "one_worker": one, "two_virtual_workers": two}


def phase_lm(model_cfg=LM_MODEL, cfg=LM):
    import jax.numpy as jnp

    heads = model_cfg["heads"]
    shape = (cfg["batch"], cfg["seq"], heads, model_cfg["dim"] // heads)
    check(compiled_with_kernel(shape, jnp.bfloat16),
          f"attention at {shape} bf16 did not compile to the Pallas kernel")
    check(compiled_with_kernel(shape, jnp.bfloat16, workers=2),
          f"attention at {shape} bf16 under vmap did not compile to the "
          "Pallas kernel")
    errors = attention_agreement(shape, jnp.bfloat16)
    check(max(errors.values()) <= ATTN_TOL,
          f"flash attention disagrees with local_attention: {errors} "
          f"(tolerance {ATTN_TOL})")
    fields = lm_train(model_cfg, cfg)
    return {"model": model_cfg, "seq": cfg["seq"], "batch": cfg["batch"],
            "window": cfg["window"], "compute_dtype": "bfloat16",
            "attention": "pallas flash kernel (tpu_custom_call), alone and "
                         "under vmap",
            "attention_shape": list(shape), "attention_tolerance": ATTN_TOL,
            "attention_max_error": errors, **fields}


# ------------------------------------------------------------------- serve


def _top2_gap(module, params, prefix):
    """Gap between the reference's two best next-token logits after
    ``prefix`` (full-context forward, no cache)."""
    import jax

    forward = jax.jit(lambda p, tokens: module.apply({"params": p}, tokens))
    logits = np.asarray(
        forward(params, np.asarray(prefix, np.int32)[None]), np.float32)[0, -1]
    best = np.partition(logits, -2)[-2:]
    return float(best[1] - best[0])


def phase_serve(model_cfg=LM_MODEL, cfg=SERVE):
    import jax

    from distkeras_tpu.models import FlaxModel, TransformerLM, greedy_generate
    from distkeras_tpu.models.adapter import TrainedModel
    from distkeras_tpu.serving import GenerateRequest, ServingEngine

    module = TransformerLM(**model_cfg)
    params = module.init(jax.random.PRNGKey(SEED),
                         np.zeros((1, 8), np.int32))["params"]
    rng = np.random.default_rng(SEED)
    n, plen, new = cfg["num_slots"], cfg["prompt_len"], cfg["new_tokens"]
    prompts = rng.integers(0, model_cfg["vocab_size"], size=(n, plen))

    engine = ServingEngine(module, params, num_slots=n)
    try:
        t0 = time.perf_counter()
        pending = [engine.submit(GenerateRequest(
            prompt=[int(t) for t in p], max_new_tokens=new)) for p in prompts]
        results = [p.result(timeout=600.0) for p in pending]
        served_s = time.perf_counter() - t0
        # the blocking convenience path, after the concurrent batch retired
        again = engine.generate(prompts[0], max_new_tokens=new, timeout=600.0)
    finally:
        engine.stop()
    check(all(r is not None for r in results), "a request timed out")
    served = [list(r.tokens) for r in results]
    check(all(len(t) == new for t in served),
          f"expected {new} new tokens each, got {[len(t) for t in served]}")
    check(list(again.tokens) == served[0],
          "generate() and submit() disagree on the same greedy request")

    reference = greedy_generate(
        TrainedModel(FlaxModel(module), params, {}), prompts, new)[:, plen:]
    near_ties = []
    for i, (got, want) in enumerate(zip(served, reference.tolist())):
        if got == want:
            continue
        pos = next(j for j in range(new) if got[j] != want[j])
        gap = _top2_gap(module, params, list(prompts[i]) + want[:pos])
        near_ties.append({"request": i, "position": pos,
                          "top2_logit_gap": gap})
        check(gap < TIE_GAP,
              f"request {i} differs from greedy_generate at new token {pos} "
              f"where the top-2 logit gap is {gap:.4f} (>= {TIE_GAP}): "
              f"served {got[pos]}, reference {want[pos]}")
    return {"model": model_cfg, "num_slots": n, "prompt_len": plen,
            "new_tokens": new, "requests": n, "tokens": n * new,
            "equal_to_greedy_generate": n - len(near_ties),
            "near_ties": near_ties, "tie_gap": TIE_GAP,
            "served_s": round(served_s, 2)}


# -------------------------------------------------------------- four chips


def phase_four_chips(cfg=TRAIN, windows=2):
    """The train job with 4 workers on 4 chips against the same epoch on one
    chip as 4 virtual workers.

    The 4-chip side is the user's call, ``dk.DOWNPOUR(num_workers=4)
    .train(df)``: the trainer gives its engine no mesh, so the engine's own
    pick of devices is what runs.  A side on fewer chips than are visible
    cannot be asked of the trainer, so those are built at engine level with
    an explicit mesh, on the rows in the order the trainer lays them out
    (``train`` does not shuffle).  Checks, as L2 distance of two centers over
    the distance travelled (``CENTER_TOL``):

    * f32: trainer on 4 chips against 1 chip x 4 virtual workers;
    * bf16, the job's dtype: 2 chips x 2 virtual workers against 1 chip x 4
      — the bf16 commit crossing chips;
    * an engine built as the trainer builds it (no mesh) repeats the
      trainer's f32 center, and after the epoch its worker-sharded leaves
      sit on 4 distinct devices (2 and 1 for the other meshes).

    bf16 trainer on 4 chips against 1 chip x 4 is printed without a bound: on
    the chip the un-batched bf16 step (one worker per device) and the vmapped
    one part by a good share of the distance travelled (PERF.md, open
    questions)."""
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.algorithms import Downpour
    from distkeras_tpu.models import CIFARCNN, FlaxModel
    from distkeras_tpu.parallel.engine import WindowedEngine
    from distkeras_tpu.parallel.mesh import make_mesh

    workers, loss = 4, "categorical_crossentropy"
    rows = workers * windows * cfg["window"] * cfg["batch"]
    x, y = cifar_like(rows, cfg["shape"], cfg["classes"], SEED)
    y = np.eye(cfg["classes"], dtype=np.float32)[y]
    module = CIFARCNN(num_classes=cfg["classes"])
    layout = (workers, windows, cfg["window"], cfg["batch"])
    flat = lambda tree: np.concatenate(
        [np.ravel(np.asarray(leaf)) for leaf in jax.tree.leaves(tree)])
    leaf_devices = {}

    def trainer_epoch(dtype):
        fields, model = downpour_job(module, loss, cfg, dk.from_numpy(x, y),
                                     rows, workers, 1, compute_dtype=dtype)
        return flat(model.params), fields["loss"][0]

    def engine_epoch(devices, dtype):
        """``devices=None``: no mesh, as the trainer builds its engine."""
        engine = WindowedEngine(
            FlaxModel(module), loss, worker_optimizer(cfg, workers),
            Downpour(cfg["window"]), num_workers=workers, metrics=(),
            compute_dtype=dtype and jax.numpy.dtype(dtype),
            mesh=devices and make_mesh(devices))
        state = engine.init_state(jax.random.PRNGKey(SEED), x[:cfg["batch"]])
        start = flat(state.center_params)
        state, stats = engine.run_epoch(state, *engine.shard_batches(
            x.reshape(layout + x.shape[1:]), y.reshape(layout + y.shape[1:])))
        mean_loss = float(np.asarray(stats["loss"], np.float32).mean())
        check(math.isfinite(mean_loss), "non-finite loss")
        check(int(state.center_rule["num_updates"]) == workers * windows,
              "commit count is not workers x windows")
        spread = {len(leaf.sharding.device_set)
                  for leaf in jax.tree.leaves(state.local_params)}
        check(spread == {devices or workers},
              f"worker-sharded leaves live on {spread} devices, expected "
              f"{devices or workers}")
        leaf_devices[f"mesh of {devices}" if devices else "no mesh"] = max(
            spread)
        return flat(engine.gather_center(state)), mean_loss, start

    def apart(a, b, start):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b - start))

    trained32, trained32_loss = trainer_epoch(None)
    picked32, _, _ = engine_epoch(None, None)
    one32, one32_loss, start = engine_epoch(1, None)
    trained16, trained16_loss = trainer_epoch("bfloat16")
    two16, two16_loss, _ = engine_epoch(2, "bfloat16")
    one16, one16_loss, _ = engine_epoch(1, "bfloat16")
    checked = {
        "f32_trainer_4x1_vs_1x4": apart(trained32, one32, start),
        "f32_trainer_vs_engine_without_mesh": apart(trained32, picked32,
                                                    start),
        "bf16_2x2_vs_1x4": apart(two16, one16, start)}
    for name, distance in checked.items():
        check(distance <= CENTER_TOL,
              f"{name}: the centers end {distance:.2e} of the distance "
              f"travelled apart (tolerance {CENTER_TOL})")
    return {"model": "CIFARCNN", "workers": workers,
            "windows_per_worker": windows, "window": cfg["window"],
            "batch": cfg["batch"], "worker_leaf_devices": leaf_devices,
            "distance_over_movement": checked, "tolerance": CENTER_TOL,
            "unbounded_reading": {
                "bf16_trainer_4x1_vs_1x4": apart(trained16, one16, start)},
            "loss": {"f32": {"trainer_4x1": trained32_loss, "1x4": one32_loss},
                     "bf16": {"trainer_4x1": trained16_loss,
                              "2x2": two16_loss, "1x4": one16_loss}}}


# -------------------------------------------------------------------- main


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: run only the four-chip commit comparison")
    args = parser.parse_args()
    t0 = time.perf_counter()

    import importlib.metadata as metadata

    import jax

    import distkeras_tpu
    from distkeras_tpu import native
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {device}. It does "
                 "not fall back to another backend.")
    if device["count"] != args.chips:
        sys.exit(f"chip_smoke.py --chips {args.chips} needs exactly "
                 f"{args.chips} chip(s); JAX found {device['count']}.")
    emit(start="chip_smoke", device=device, chips=args.chips,
         compile_cache_dir=enable_compile_cache(
             os.path.dirname(os.path.abspath(__file__))),
         versions={"python": sys.version.split()[0],
                   "distkeras_tpu": distkeras_tpu.__version__,
                   **{pkg: metadata.version(pkg) for pkg in
                      ("jax", "jaxlib", "libtpu", "flax", "optax")}},
         native_dataloader="c++" if native.available() else "numpy fallback")

    if args.chips == 4:
        timed_phase("four_chips", phase_four_chips)
    else:
        timed_phase("train", phase_train)
        timed_phase("lm", phase_lm)
        timed_phase("serve", phase_serve)
    emit(total_s=round(time.perf_counter() - t0, 2))
    emit(ok=True, device=device)


if __name__ == "__main__":
    main()
