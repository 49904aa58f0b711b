"""End-to-end trainer tests on the faked 8-device CPU mesh.

The reference's only 'tests' were its example notebooks run under Spark
local[N] (SURVEY.md §4); these tests are the pytest form of that: every
trainer trains a small model on a toy problem end-to-end and must (a) return
a working model, (b) beat chance accuracy, (c) keep its reference API
surface (history, training time, parameter-server counters).
"""

import jax
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.frame import from_numpy
from distkeras_tpu.models import (
    CIFARCNN,
    MLP,
    MNISTCNN,
    FlaxModel,
    ResNet20,
    TextCNN,
)
from distkeras_tpu.predictors import ModelPredictor


def make_df(toy):
    x, y, onehot = toy
    return from_numpy(x, onehot)


def model():
    return FlaxModel(MLP(features=(16,), num_classes=2))


def accuracy_of(trained, toy):
    x, y, _ = toy
    preds = trained.predict(x)
    return float(np.mean(np.argmax(preds, -1) == y))


def test_single_trainer_end_to_end(toy_classification):
    df = make_df(toy_classification)
    t = dk.SingleTrainer(model(), loss="categorical_crossentropy",
                         worker_optimizer=("sgd", {"learning_rate": 0.1}),
                         batch_size=32, num_epoch=12)
    trained = t.train(df)
    assert accuracy_of(trained, toy_classification) > 0.85
    assert t.get_training_time() > 0
    assert len(t.get_history()["loss"]) == 12
    # loss decreases
    h = t.get_history()["loss"]
    assert h[-1] < h[0]


@pytest.mark.parametrize("trainer_cls,kwargs", [
    (dk.DOWNPOUR, {"communication_window": 4}),
    (dk.ADAG, {"communication_window": 4}),
    (dk.AEASGD, {"communication_window": 4, "rho": 1.0, "learning_rate": 0.05}),
    (dk.EAMSGD, {"communication_window": 4, "rho": 1.0, "learning_rate": 0.05,
                 "momentum": 0.5}),
    (dk.DynSGD, {"communication_window": 4}),
])
def test_distributed_trainers_converge(toy_classification, trainer_cls, kwargs):
    df = make_df(toy_classification)
    t = trainer_cls(model(), loss="categorical_crossentropy",
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=4, batch_size=16, num_epoch=10, **kwargs)
    trained = t.train(df)
    assert accuracy_of(trained, toy_classification) > 0.85
    assert t.num_updates > 0  # parameter-server counter advanced
    assert t.parameter_server.get_model() is trained


def test_averaging_trainer(toy_classification):
    df = make_df(toy_classification)
    t = dk.AveragingTrainer(model(), loss="categorical_crossentropy",
                            worker_optimizer=("sgd", {"learning_rate": 0.1}),
                            num_workers=4, batch_size=16, num_epoch=10)
    trained = t.train(df)
    assert accuracy_of(trained, toy_classification) > 0.8


def test_ensemble_trainer_returns_n_models(toy_classification):
    df = make_df(toy_classification)
    t = dk.EnsembleTrainer(model(), loss="categorical_crossentropy",
                           worker_optimizer=("sgd", {"learning_rate": 0.1}),
                           num_models=3, batch_size=16, num_epoch=6)
    models = t.train(df)
    assert len(models) == 3
    for m in models:
        assert accuracy_of(m, toy_classification) > 0.7
    # independent models differ
    p0 = jax.tree.leaves(models[0].params)[0]
    p1 = jax.tree.leaves(models[1].params)[0]
    assert not np.allclose(p0, p1)


def test_ensemble_trainer_keras_returns_n_keras_models(toy_classification):
    """Reference parity: a Keras model in means N trained Keras models out
    (the reference's EnsembleTrainer returned deserialised Keras models).
    Each member must be an independent clone carrying ITS worker's weights —
    not N handles onto one mutated model."""
    keras = pytest.importorskip("keras")

    x, y, onehot = toy_classification
    km = keras.Sequential([
        keras.layers.Input(shape=(8,)),
        keras.layers.Dense(16, activation="relu"),
        keras.layers.Dense(2, activation="softmax"),
    ])
    t = dk.EnsembleTrainer(km, loss="categorical_crossentropy",
                           worker_optimizer=("sgd", {"learning_rate": 0.1}),
                           num_models=3, batch_size=16, num_epoch=6)
    models = t.train(from_numpy(x, onehot))
    assert len(models) == 3
    assert all(isinstance(m, keras.Model) for m in models)
    assert all(m is not km for m in models)
    for m in models:
        preds = np.asarray(m.predict(x, verbose=0))
        assert float(np.mean(np.argmax(preds, -1) == y)) > 0.7
    # independent members: first kernel differs between clones
    w0 = models[0].get_weights()[0]
    w1 = models[1].get_weights()[0]
    assert not np.allclose(w0, w1)


def test_parameter_server_pollable_mid_train(toy_classification):
    """Reference parity: the socket PS answered ``num_updates`` queries
    WHILE training ran.  The facade must do the same — epoch boundaries
    refresh a live device-side copy of the commit counter (the epoch state
    itself is donated, so the facade cannot just hold a reference), and a
    concurrent thread polling the trainer sees monotone, eventually
    non-zero counts before ``train`` returns."""
    import threading
    import time

    df = make_df(toy_classification)
    t = dk.DOWNPOUR(model(), loss="categorical_crossentropy",
                    worker_optimizer=("sgd", {"learning_rate": 0.1}),
                    num_workers=4, batch_size=16, num_epoch=20,
                    communication_window=2)
    samples, done = [], threading.Event()

    def poll():
        while not done.is_set():
            ps = t.parameter_server
            if ps is not None:
                samples.append(ps.num_updates)
            time.sleep(0.001)

    poller = threading.Thread(target=poll)
    poller.start()
    try:
        t.train(df)
    finally:
        done.set()
        poller.join()
    assert samples, "poller never saw the parameter server"
    assert all(b >= a for a, b in zip(samples, samples[1:])), "counter regressed"
    assert samples[-1] > 0  # observed live progress before train() returned
    assert t.num_updates >= samples[-1]


def test_downpour_determinism(toy_classification):
    """XLA collectives are deterministic — same seed, same result (the
    property the reference's hogwild PS could never have; SURVEY.md §5.2)."""
    df = make_df(toy_classification)

    def run():
        t = dk.DOWNPOUR(model(), loss="categorical_crossentropy",
                        worker_optimizer=("sgd", {"learning_rate": 0.05}),
                        num_workers=4, batch_size=16, num_epoch=2,
                        communication_window=4, seed=7)
        return t.train(df)

    a, b = run(), run()
    for la, lb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_staleness_schedule_dynsgd(toy_classification):
    """Heterogeneous commit schedules: the deterministic async simulation."""
    df = make_df(toy_classification)
    t = dk.DynSGD(model(), loss="categorical_crossentropy",
                  worker_optimizer=("sgd", {"learning_rate": 0.1}),
                  num_workers=4, batch_size=16, num_epoch=8,
                  commit_schedule=[2, 4, 4, 8])
    trained = t.train(df)
    assert accuracy_of(trained, toy_classification) > 0.8
    assert t.num_updates > 0


def test_predictor_integration(toy_classification):
    x, y, onehot = toy_classification
    df = make_df(toy_classification)
    t = dk.SingleTrainer(model(), loss="categorical_crossentropy",
                         worker_optimizer=("sgd", {"learning_rate": 0.1}),
                         batch_size=32, num_epoch=8)
    trained = t.train(df)
    pred_df = ModelPredictor(trained).predict(df)
    assert "prediction" in pred_df
    out = dk.LabelIndexTransformer(2, input_col="prediction", output_col="p_idx").transform(pred_df)
    out = out.with_column("y", y)
    acc = dk.AccuracyEvaluator(prediction_col="p_idx", label_col="y").evaluate(out)
    assert acc > 0.85


# The six jobs of the paper (ROADMAP.md R12's table, the source of the
# benchmark's future cells): model, trainer with its rule's knobs, worker
# optimizer, classes; rows are the table's shapes and the compute dtype is
# bfloat16 in all six.  The table's per-worker batch (128-512) and window (16;
# 32 for the single trainer) are cut to what a CPU runs in seconds.
PAPER_JOBS = {
    "cifar_cnn_downpour": (
        CIFARCNN(), dk.DOWNPOUR, {},
        ("sgd", {"learning_rate": 0.05, "momentum": 0.9}), (32, 32, 3), 10),
    "mnist_mlp_single": (
        MLP(), dk.SingleTrainer, None,
        ("sgd", {"learning_rate": 0.1}), (784,), 10),
    "mnist_cnn_downpour": (
        MNISTCNN(), dk.DOWNPOUR, {},
        ("sgd", {"learning_rate": 0.05}), (28, 28, 1), 10),
    "cifar_cnn_aeasgd": (
        CIFARCNN(), dk.AEASGD, {"rho": 5.0, "learning_rate": 0.05},
        ("sgd", {"learning_rate": 0.05}), (32, 32, 3), 10),
    "cifar_resnet20_adag": (
        ResNet20(), dk.ADAG, {},
        ("sgd", {"learning_rate": 0.1, "momentum": 0.9}), (32, 32, 3), 10),
    "imdb_textcnn_dynsgd": (
        TextCNN(vocab_size=20000, num_classes=2), dk.DynSGD, {},
        ("adam", {"learning_rate": 1e-3}), (256,), 2),
}


@pytest.mark.parametrize("job", sorted(PAPER_JOBS))
def test_paper_job_trains_through_its_trainer(job):
    """Each paper job through its public trainer and ``train(df)``: every
    epoch's loss is finite and the parameter server counted one commit per
    worker and window."""
    module, trainer_cls, rule_knobs, optimizer, shape, classes = PAPER_JOBS[job]
    workers, batch, window, windows, epochs = 2, 4, 2, 2, 2
    single = rule_knobs is None
    rows = (1 if single else workers) * windows * window * batch
    rng = np.random.default_rng(0)
    if job == "imdb_textcnn_dynsgd":  # token ids
        x = rng.integers(0, 1000, size=(rows,) + shape).astype(np.int32)
    else:
        x = (0.5 * rng.normal(size=(rows,) + shape)).astype(np.float32)
    onehot = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, size=rows)]
    kwargs = {} if single else dict(
        rule_knobs, num_workers=workers, communication_window=window)
    t = trainer_cls(FlaxModel(module), loss="categorical_crossentropy",
                    worker_optimizer=optimizer, metrics=(), batch_size=batch,
                    num_epoch=epochs, compute_dtype="bfloat16", **kwargs)
    trained = t.train(from_numpy(x, onehot))
    losses = t.get_history()["loss"]
    assert len(losses) == epochs and np.all(np.isfinite(losses)), losses
    assert np.asarray(trained.predict(x[:batch])).shape == (batch, classes)
    if not single:
        assert t.num_updates == workers * windows * epochs
