"""Tour of the parallelism axes beyond plain data parallelism.

The reference's only axis was Spark-task data parallelism; this example runs
the rebuild's seven extra axes on a faked 8-device CPU mesh so it works on
any machine (swap to real chips by deleting the two config lines):

  1. virtual workers      — more logical workers than devices (the analogue
                            of the reference's ``parallelism_factor``)
  2. sequence parallelism — ring attention over a (workers x seq) mesh
  3. tensor parallelism   — GSPMD engine over a (workers x model) mesh
  4. staleness simulation — per-worker commit periods (deterministic
                            asynchrony), here combined with TP
  5. pipeline parallelism — microbatch ppermute pipeline over a
                            (workers x stages) mesh (staged transformer)
  6. expert parallelism   — Switch MoE with the expert stacks sharded over
                            the model axis (GSPMD placement override)
  7. FSDP / ZeRO-3        — the center variable sharded over the workers
                            axis (gather-at-use) instead of replicated
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

if os.environ.get("DK_TPU") != "1":  # delete these two lines on real chips
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

import numpy as np


def main():
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    import distkeras_tpu as dk
    from distkeras_tpu.models import FlaxModel, MLP, TransformerClassifier

    print(f"devices: {jax.device_count()}")

    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 16)).astype(np.float32)
    y = np.argmax(x @ rng.normal(size=(16, 4)), axis=1).astype(np.int32)
    df = dk.from_numpy(x, np.eye(4, dtype=np.float32)[y])

    def report(tag, trainer, trained, data=x, labels=y):
        preds = np.argmax(trained.predict(data), -1)
        acc = np.mean(preds == labels)
        print(f"{tag:28s} acc={acc:.3f} time={trainer.get_training_time():.1f}s")

    # 1. virtual workers: 16 logical workers on 8 devices
    t = dk.DOWNPOUR(FlaxModel(MLP(features=(64,), num_classes=4)),
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=16, batch_size=16, num_epoch=5,
                    communication_window=4)
    report("16 virtual workers / 8 dev", t, t.train(df))

    # 2. sequence parallelism: transformer tokens sharded 2-way
    tokens = rng.integers(0, 64, size=(1024, 32)).astype(np.int32)
    ty = ((tokens == 7).sum(1) > (tokens == 3).sum(1)).astype(np.int32)
    tdf = dk.from_numpy(tokens, np.eye(2, dtype=np.float32)[ty])
    t = dk.DOWNPOUR(FlaxModel(TransformerClassifier(
                        vocab_size=64, num_classes=2, dim=32, heads=2,
                        num_layers=1, max_len=64, seq_axis="seq")),
                    worker_optimizer=("adam", {"learning_rate": 3e-3}),
                    num_workers=4, batch_size=16, num_epoch=10,
                    communication_window=2, seq_shards=2)
    report("ring attention 4w x 2seq", t, t.train(tdf), tokens, ty)

    # 3. tensor parallelism: same trainer API, GSPMD engine
    t = dk.DOWNPOUR(FlaxModel(MLP(features=(64,), num_classes=4)),
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=4, batch_size=16, num_epoch=5,
                    communication_window=4, tp_shards=2)
    report("tensor parallel 4w x 2mp", t, t.train(df))

    # 4. deterministic asynchrony (per-worker commit periods) under TP
    t = dk.DynSGD(FlaxModel(MLP(features=(64,), num_classes=4)),
                  worker_optimizer=("sgd", {"learning_rate": 0.1}),
                  num_workers=4, batch_size=16, num_epoch=5,
                  communication_window=4, tp_shards=2,
                  commit_schedule=[3, 4, 5, 6])
    report("DynSGD staleness sim + TP", t, t.train(df))

    # 5. pipeline parallelism: staged transformer, 2 workers x 4 stages
    from distkeras_tpu.models import StagedTransformer

    t = dk.DOWNPOUR(StagedTransformer(vocab_size=64, num_classes=2, dim=32,
                                      heads=2, num_stages=4,
                                      blocks_per_stage=1, max_len=64),
                    worker_optimizer=("adam", {"learning_rate": 2e-3}),
                    num_workers=2, batch_size=16, num_epoch=10,
                    communication_window=2, pipeline_stages=4)
    report("pipeline 2w x 4 stages", t, t.train(tdf), tokens, ty)

    # 6. expert parallelism: Switch MoE, experts sharded over the model axis
    from distkeras_tpu.models import MoETransformerClassifier, expert_partition

    t = dk.DOWNPOUR(FlaxModel(MoETransformerClassifier(
                        vocab_size=64, num_classes=2, dim=32, heads=2,
                        num_layers=1, num_experts=4, mlp_ratio=2,
                        max_len=64)),
                    worker_optimizer=("adam", {"learning_rate": 2e-3}),
                    num_workers=4, batch_size=16, num_epoch=10,
                    communication_window=2, tp_shards=2,
                    tp_spec_fn=expert_partition(4))
    report("Switch MoE 4w x 2experts", t, t.train(tdf), tokens, ty)

    # 7. FSDP / ZeRO-3: the center variable itself sharded over the workers
    #    axis (all-gather at pull, reduce-scatter at commit) — same
    #    trajectory as plain DP, 1/num_devices the center HBM
    t = dk.DOWNPOUR(FlaxModel(MLP(features=(64,), num_classes=4)),
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=8, batch_size=16, num_epoch=5,
                    communication_window=4, fsdp=True)
    report("FSDP-sharded center 8w", t, t.train(df))


if __name__ == "__main__":
    main()
