"""Accuracy-proof harness (examples/accuracy.py): the proxy datasets.

The floors themselves (every trainer family within its gap of the
SingleTrainer yardstick) are judged on a chip run of ``examples/accuracy.py``
— CIFAR-scale convs do not train in test time on the CPU, and that run is
not measured on today's code.  What the suite pins here is that the proxy
datasets are deterministic, class-informative, and GENUINELY HARD (their
Bayes-style oracles land mid-80s/low-90s, so a saturated accuracy table
would mean the task regressed to trivial).
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "examples"))

from accuracy import make_cifar_proxy, make_imdb_proxy

def test_cifar_proxy_deterministic_and_shaped():
    x1, y1 = make_cifar_proxy(64, seed=0)
    x2, y2 = make_cifar_proxy(64, seed=0)
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert x1.shape == (64, 32, 32, 3) and x1.dtype == np.float32
    assert x1.min() >= 0.0 and x1.max() <= 1.0
    x3, _ = make_cifar_proxy(64, seed=1)
    assert not np.array_equal(x1, x3)


def test_imdb_proxy_deterministic_and_shaped():
    x1, y1 = make_imdb_proxy(64, seed=0)
    x2, y2 = make_imdb_proxy(64, seed=0)
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert x1.shape == (64, 256) and x1.dtype == np.int32
    assert x1.min() >= 100 and x1.max() < 20000


def test_cifar_proxy_is_orientation_separable_but_not_trivially():
    """The class signal is real and pixel-level-nonlinear — and the
    orientation jitter means even an oriented-energy oracle cannot
    saturate: the proxy has genuine headroom below 1.0."""
    x, y = make_cifar_proxy(2048, seed=0, num_classes=2)
    gray = x.mean(-1)
    # phase randomisation: class-mean images carry almost no signal
    m0, m1 = gray[y == 0].mean(0), gray[y == 1].mean(0)
    assert np.abs(m0 - m1).max() < 0.15
    # oriented gradient energy still separates the two orientations (the
    # task is learnable), but jitter + noise keep it off ceiling
    gx = np.abs(np.diff(gray, axis=2)).mean((1, 2))
    gy = np.abs(np.diff(gray, axis=1)).mean((1, 2))
    stat = gx - gy  # class 0 (theta=0): vertical stripes -> gx >> gy
    acc = max(((stat > 0) == (y == 0)).mean(), ((stat > 0) == (y == 1)).mean())
    assert acc > 0.85


def test_imdb_proxy_counting_oracle_is_non_saturating():
    """The Bayes-style decision (majority of own-vs-other lexicon hits,
    ties split) must land near its designed 0.914 — hard enough that a
    trained model cannot saturate, easy enough that it must beat 0.8."""
    x, y = make_imdb_proxy(20000, seed=0)
    lex0 = ((x >= 100) & (x < 200)).sum(axis=1)
    lex1 = ((x >= 200) & (x < 300)).sum(axis=1)
    own = np.where(y == 0, lex0, lex1)
    other = np.where(y == 0, lex1, lex0)
    oracle = (own > other).mean() + 0.5 * (own == other).mean()
    assert 0.88 < oracle < 0.94, oracle
    # confusers are REAL: other-lexicon tokens appear in a sizable minority
    assert 0.15 < (other > 0).mean() < 0.75
    # every sequence plants at least one own-lexicon token
    assert own.min() >= 1
