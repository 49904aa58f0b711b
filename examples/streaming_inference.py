"""High-throughput streaming inference — the reference's Kafka pipeline
notebook (``examples/`` Kafka producer + inference consumer) without the
Kafka dependency.

Default: a producer thread emits feature batches onto a queue (stand-in for
a Kafka topic; swap in ``kafka-python`` consumers unchanged — the prediction
loop only sees an iterator of batches).  With ``--source tcp://host:port``
the consumer instead drains a *separate producer process*
(``examples/kafka_producer.py``) over the package wire codec — the real
cross-process pipeline.  Either way the consumer runs the jitted model
forward pass per batch and reports sustained rows/sec.
"""

import argparse
import os
import queue
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def tcp_batches(addr: str):
    """Yield batches from a kafka_producer.py --port serving at tcp://host:port."""
    from distkeras_tpu.networking import connect, recv_data

    host, port = addr.removeprefix("tcp://").rsplit(":", 1)
    sock = connect(host, int(port))
    try:
        while True:
            batch = recv_data(sock)
            if batch is None:
                return
            yield batch
    finally:
        sock.close()


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--source", default=None,
                        help="tcp://host:port of a running kafka_producer.py "
                             "(default: in-process producer thread)")
    args = parser.parse_args()
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    import distkeras_tpu as dk
    from distkeras_tpu.models import MLP, FlaxModel
    from distkeras_tpu.predictors import ModelPredictor

    # Train a small model first (the pipeline's "offline" phase).
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4096, 32)).astype(np.float32)
    w = rng.normal(size=(32, 4))
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    df = dk.from_numpy(x, y)
    df = dk.OneHotTransformer(4, input_col="label", output_col="label_oh").transform(df)
    trained = dk.SingleTrainer(FlaxModel(MLP(features=(64,), num_classes=4)),
                               loss="categorical_crossentropy",
                               worker_optimizer=("sgd", {"learning_rate": 0.1}),
                               label_col="label_oh", batch_size=64,
                               num_epoch=3).train(df)
    predictor = ModelPredictor(trained, batch_size=1024)

    if args.source:
        stream = tcp_batches(args.source)
    else:
        # "Kafka topic": a bounded queue fed by a producer thread.
        topic: "queue.Queue" = queue.Queue(maxsize=64)
        n_batches, batch_rows = 200, 1024

        def producer():
            for _ in range(n_batches):
                topic.put(rng.normal(size=(batch_rows, 32)).astype(np.float32))
            topic.put(None)  # end-of-stream marker

        threading.Thread(target=producer, daemon=True).start()
        stream = iter(topic.get, None)

    rows = 0
    t0 = time.perf_counter()
    for batch in stream:
        out = predictor.predict(dk.from_numpy(batch))
        rows += len(out)
    dt = time.perf_counter() - t0
    print(f"streamed {rows} rows in {dt:.2f}s -> {rows/dt:,.0f} rows/sec")


if __name__ == "__main__":
    main()
