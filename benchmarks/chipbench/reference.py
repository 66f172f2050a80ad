"""The plain float32 reference of the paper's MLP networks.

It follows the math of the program's ``PlainEngine`` (``nn/engine.py``)
without importing it: ReLU hidden layers and the paper's smx output
``relu(z) / (sum relu(z) + 1e-2)``.  Every matmul runs at full float32
precision (a TPU otherwise passes float32 matmuls through bfloat16).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _smx(z):
    """(probabilities, denominator): relu(z) / (sum relu(z) + 1e-2)."""
    r = jnp.maximum(z, 0)
    s = jnp.sum(r, axis=-1, keepdims=True) + 1e-2
    return r / s, s


@jax.jit
def _predict(params, X):
    """(probabilities, the smx denominators)."""
    h = X
    for i in range(len(params)):
        z = h @ params[f"w{i}"]
        h = jnp.maximum(z, 0)
    return _smx(z)


def _f32(tree):
    return {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}


def predict(params: dict, X) -> tuple[np.ndarray, np.ndarray]:
    """(probabilities, each query's smx denominator)."""
    with jax.default_matmul_precision("highest"):
        p, s = _predict(_f32(params), jnp.asarray(X, jnp.float32))
    return np.asarray(p, np.float64), np.asarray(s, np.float64)


def forward_flops(dims) -> int:
    """Multiply-adds of one sample's forward pass, counted as 2 operations
    each: 2 x (number of weights)."""
    return 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))

