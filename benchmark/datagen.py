"""Seeded inputs for the training cells, made in bulk with NumPy.

A configuration's file names one of these makers (``"data": {"maker":
"datagen:<function>", ...}``); a later PR with another kind of data adds a
module of its own beside this one.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: rows filled by one child seed; fixed, so the rows do not depend on how
#: many threads fill them
CHUNK_ROWS = 8192


def _threads():
    return max(1, min(16, (os.cpu_count() or 2) - 1))


def cifar_like(rows, seed, *, shape, classes, scale):
    """Image-shaped float32 rows with a learnable rule (copied from
    ``chip_smoke.py::cifar_like``): each class has a fixed random template, a
    row is its class's template plus unit noise, all times ``scale``.  Labels
    are one-hot float32.  Filled in fixed chunks, each from its own child of
    the seed, on a few threads (NumPy's generators release the GIL)."""
    shape = tuple(shape)
    chunks = -(-rows // CHUNK_ROWS)
    head, *children = np.random.SeedSequence(seed).spawn(1 + chunks)
    rng = np.random.default_rng(head)
    templates = rng.standard_normal(size=(classes,) + shape, dtype=np.float32)
    y = rng.integers(0, classes, size=rows)
    x = np.empty((rows,) + shape, np.float32)

    def fill(i):
        part = slice(i * CHUNK_ROWS, min(rows, (i + 1) * CHUNK_ROWS))
        np.random.default_rng(children[i]).standard_normal(
            dtype=np.float32, out=x[part])
        x[part] += templates[y[part]]
        x[part] *= scale

    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(fill, range(chunks)))
    return x, np.eye(classes, dtype=np.float32)[y]


def zipf_tokens(rows, seed, *, seq, vocab, exponent):
    """Token sequences drawn from a Zipf law over the vocabulary (rank r has
    weight r ** -exponent; ranks are mapped to token ids by a seeded
    permutation), so that the loss can fall below ln(vocab) and a falling
    loss means something.  Inputs are tokens 0..seq-1 of each row, labels the
    next tokens."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    cdf = np.cumsum(weights / weights.sum())
    ranks = np.searchsorted(cdf, rng.random(size=(rows, seq + 1)))
    tokens = rng.permutation(vocab)[np.minimum(ranks, vocab - 1)].astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]
