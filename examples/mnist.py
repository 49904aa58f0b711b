"""MNIST end-to-end pipeline — the reference's flagship example, TPU-native.

Mirrors ``examples/mnist.py`` / ``mnist.ipynb`` of dist-keras: read the raw
dataset into a DataFrame, normalise + one-hot with transformers, train with
SingleTrainer then the async trainers (DOWNPOUR, AEASGD, ADAG), then predict
and evaluate — the whole flow staying on DataFrames.

Run:  python examples/mnist.py [--workers N] [--epochs E]

Dataset: uses ``keras.datasets.mnist`` when the archive is cached locally;
otherwise falls back to scikit-learn's bundled 8x8 digits (offline-friendly),
which exercises the identical pipeline at smaller scale.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def load_dataset(force_digits: bool = False):
    """Returns (name, features, labels, max_value, image_shape).

    Only uses MNIST when the archive is already cached: ``load_data()`` would
    otherwise try to download, which hangs in offline environments.
    ``force_digits`` pins the scikit-learn fallback regardless of cache state
    (tests need machine-independent data).
    """
    cache = os.path.expanduser("~/.keras/datasets/mnist.npz")
    if not force_digits and os.path.exists(cache):
        with np.load(cache) as d:
            x, y = d["x_train"], d["y_train"]
        x = x.reshape(len(x), -1).astype(np.float32)
        return "mnist", x, y.astype(np.int32), 255.0, (28, 28, 1)
    from sklearn.datasets import load_digits

    d = load_digits()
    return ("digits", d.data.astype(np.float32), d.target.astype(np.int32),
            16.0, (8, 8, 1))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workers", type=int, default=None)
    parser.add_argument("--epochs", type=int, default=5)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--dispatch-epochs", type=int, default=1,
                        help="epochs per device dispatch (>1: one jitted "
                             "multi-epoch program with on-device reshuffle)")
    parser.add_argument("--digits", action="store_true",
                        help="pin the sklearn digits fallback regardless of "
                             "any cached MNIST (machine-independent runs)")
    args = parser.parse_args()
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))

    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.models import MLP, FlaxModel

    num_workers = args.workers or jax.device_count()
    _, x, y, max_val, img_shape = load_dataset(force_digits=args.digits)
    num_features = x.shape[1]
    print(f"dataset: {len(x)} samples, {num_features} features, "
          f"{num_workers} workers on {jax.default_backend()}")

    # 1. Raw data -> DataFrame (the reference reads a CSV into Spark here).
    df = dk.from_numpy(x, y, features_col="features_raw", label_col="label")

    # 2. Feature engineering with transformers (reference: MinMax + OneHot).
    df = dk.MinMaxTransformer(0.0, 1.0, 0.0, max_val,
                              input_col="features_raw",
                              output_col="features").transform(df)
    df = dk.OneHotTransformer(10, input_col="label",
                              output_col="label_encoded").transform(df)
    train_df, test_df = df.split(0.8, seed=0)
    print(f"train/test: {len(train_df)}/{len(test_df)}")

    def fresh_model():
        return FlaxModel(MLP(features=(256, 128), num_classes=10))

    def evaluate(trained) -> float:
        pred = dk.ModelPredictor(trained, features_col="features").predict(test_df)
        pred = dk.LabelIndexTransformer(10, input_col="prediction",
                                        output_col="prediction_index").transform(pred)
        return dk.AccuracyEvaluator(prediction_col="prediction_index",
                                    label_col="label").evaluate(pred)

    results = {}

    # 3. Baseline: SingleTrainer (reference experiment table row 1).
    trainer = dk.SingleTrainer(fresh_model(), loss="categorical_crossentropy",
                               worker_optimizer=("sgd", {"learning_rate": 0.1}),
                               features_col="features", label_col="label_encoded",
                               batch_size=args.batch_size, num_epoch=args.epochs,
                               dispatch_epochs=args.dispatch_epochs)
    results["SingleTrainer"] = (evaluate(trainer.train(train_df)),
                                trainer.get_training_time())

    # 4. Async data-parallel trainers.  The LR *scaling rules* follow
    # examples/experiments.py (the floor-enforced README table); windows
    # here keep this example's own shorter settings.  DOWNPOUR's commit
    # adds the SUM of per-worker window deltas, so its worker lr divides
    # by the worker count to keep the center step at the base lr; ADAG
    # pre-normalises each commit by the window, so its lr scales by
    # window/num_workers instead.  AEASGD's elastic pull is self-limiting.
    adag_window = 8
    for name, cls, kw in [
        ("DOWNPOUR", dk.DOWNPOUR,
         {"worker_optimizer": ("adam", {"learning_rate": 1e-3 / num_workers}),
          "communication_window": 5}),
        ("AEASGD", dk.AEASGD,
         {"worker_optimizer": ("sgd", {"learning_rate": 0.1}),
          "communication_window": 16, "rho": 1.0, "learning_rate": 0.05}),
        ("ADAG", dk.ADAG,
         {"worker_optimizer": ("adam",
                               {"learning_rate": 1e-3 * adag_window / num_workers}),
          "communication_window": adag_window}),
    ]:
        trainer = cls(fresh_model(), loss="categorical_crossentropy",
                      features_col="features", label_col="label_encoded",
                      num_workers=num_workers, batch_size=args.batch_size,
                      num_epoch=args.epochs,
                      dispatch_epochs=args.dispatch_epochs, **kw)
        acc = evaluate(trainer.train(train_df))
        results[name] = (acc, trainer.get_training_time())
        print(f"  {name}: parameter-server updates = {trainer.num_updates}")

    print(f"\n{'trainer':<16} {'accuracy':>9} {'time (s)':>9}")
    for name, (acc, t) in results.items():
        print(f"{name:<16} {acc:>9.4f} {t:>9.2f}")


if __name__ == "__main__":
    main()
