"""Inputs and weights of a run, made from its seed.

A copy of the program's ``MNISTLike`` generator and ``mlp_net_init``
(``src/repro/train/data.py``, ``src/repro/train/paper_ml.py``), kept here
so that a change to the program cannot move what the benchmark feeds it.
Rows are drawn without replacement: every row a run feeds differs.
"""
from __future__ import annotations

import zlib

import numpy as np


def sub_seed(seed: int, label: str) -> int:
    """A 31-bit seed for one use (``label``) of the run's seed, which may
    be any non-negative integer."""
    ss = np.random.SeedSequence([seed % (1 << 64), zlib.crc32(label.encode())])
    return int(ss.generate_state(1)[0] >> 1)


class MNISTLike:
    """784-feature, 10-class synthetic images: class templates plus noise
    (the program's ``MNISTLike``), served as a seeded permutation of its
    rows, so consecutive batches never repeat a row within ``n`` rows."""

    def __init__(self, seed: int, n: int = 8192, features: int = 784,
                 classes: int = 10):
        rng = np.random.RandomState(seed)
        self.classes = classes
        self.templates = rng.randn(classes, features) * 0.8
        self.labels = rng.randint(0, classes, n)
        self.X = (self.templates[self.labels]
                  + rng.randn(n, features) * 0.7).astype(np.float64)
        self.order = rng.permutation(n)

    def rows(self, start: int, count: int) -> np.ndarray:
        """Indices of rows ``start .. start + count`` of the permuted
        stream (it wraps after ``n`` rows)."""
        return self.order[np.arange(start, start + count) % len(self.order)]

    def queries(self, start: int, count: int) -> np.ndarray:
        return self.X[self.rows(start, count)]


def mlp_net_init(seed: int, dims) -> dict:
    """The program's ``mlp_net_init``: w_i ~ N(0, 1/dims[i]), float64."""
    rng = np.random.RandomState(seed)
    return {f"w{i}": (rng.randn(dims[i], dims[i + 1])
                      / np.sqrt(dims[i])).astype(np.float64)
            for i in range(len(dims) - 1)}
