"""API tour — the reference's ``workflow.ipynb`` as a runnable script.

Walks every public surface: DataFrame construction, transformers, all trainer
families, prediction, evaluation, serialization, and checkpoint/resume.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.models import MLP, FlaxModel

    print(f"backend: {jax.default_backend()}, devices: {jax.device_count()}")

    # ---- DataFrames ------------------------------------------------------
    rng = np.random.default_rng(0)
    n = 2048
    x = rng.normal(size=(n, 16)).astype(np.float32)
    w = rng.normal(size=(16, 3))
    y = np.argmax(x @ w + 0.3 * rng.normal(size=(n, 3)), axis=1).astype(np.int32)
    df = dk.from_numpy(x, y)
    print(df)

    # row-wise access, Spark style
    first = df.first()
    print("first row label:", first.label)

    # ---- Transformers ----------------------------------------------------
    df = dk.StandardScaleTransformer(input_col="features",
                                     output_col="features_std").transform(df)
    df = dk.OneHotTransformer(3, input_col="label",
                              output_col="label_oh").transform(df)
    train_df, test_df = df.split(0.85, seed=1)

    def fresh():
        return FlaxModel(MLP(features=(32,), num_classes=3))

    common = dict(loss="categorical_crossentropy",
                  worker_optimizer=("sgd", {"learning_rate": 0.1}),
                  features_col="features_std", label_col="label_oh",
                  batch_size=32, num_epoch=5)

    # ---- Every trainer family -------------------------------------------
    workers = min(4, jax.device_count())
    trainers = {
        "SingleTrainer": dk.SingleTrainer(fresh(), **common),
        "AveragingTrainer": dk.AveragingTrainer(fresh(), num_workers=workers, **common),
        "DOWNPOUR": dk.DOWNPOUR(fresh(), num_workers=workers,
                                communication_window=5, **common),
        "AEASGD": dk.AEASGD(fresh(), num_workers=workers,
                            communication_window=8, rho=1.0, learning_rate=0.05, **common),
        "EAMSGD": dk.EAMSGD(fresh(), num_workers=workers,
                            communication_window=8, rho=1.0, learning_rate=0.05,
                            momentum=0.8, **common),
        "ADAG": dk.ADAG(fresh(), num_workers=workers,
                        communication_window=8, **common),
        "DynSGD": dk.DynSGD(fresh(), num_workers=workers,
                            communication_window=5, **common),
    }
    for name, trainer in trainers.items():
        trained = trainer.train(train_df)
        pred = dk.ModelPredictor(trained, features_col="features_std").predict(test_df)
        pred = dk.LabelIndexTransformer(3, input_col="prediction",
                                        output_col="pidx").transform(pred)
        acc = dk.AccuracyEvaluator(prediction_col="pidx", label_col="label").evaluate(pred)
        print(f"{name:<18} acc={acc:.4f} time={trainer.get_training_time():.2f}s")

    # ---- Ensembles -------------------------------------------------------
    ensemble = dk.EnsembleTrainer(fresh(), num_models=3, **common).train(train_df)
    print(f"ensemble of {len(ensemble)} models trained")

    # ---- Checkpoint / resume --------------------------------------------
    with tempfile.TemporaryDirectory() as ckpt_dir:
        t = dk.DOWNPOUR(fresh(), num_workers=workers, communication_window=5,
                        checkpoint_dir=ckpt_dir, **common)
        t.train(train_df)
        from distkeras_tpu.checkpoint import latest_step

        print("checkpoints up to epoch:", latest_step(ckpt_dir))

    print("workflow complete")


if __name__ == "__main__":
    main()
