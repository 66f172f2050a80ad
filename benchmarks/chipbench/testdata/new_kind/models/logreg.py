"""The paper's logistic-regression predictor (``"model": "logreg"``):
sig(X w) over ``features`` inputs, with the paper's piecewise-linear
sigmoid clip(z + 1/2, 0, 1), served through the program's
``paper_ml.reg_predict(..., logistic=True)``.

A fixture of ``test_new_kind.py``: a second model kind that joins the
benchmark as new files only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

REHEARSE_BATCH = 16


class Gaussian:
    """``n`` standard normal rows from the seed, served in a seeded order
    (it wraps after ``n`` rows)."""

    def __init__(self, seed: int, features: int, n: int = 1024):
        rng = np.random.RandomState(seed)
        self.X = rng.randn(n, features)
        self.order = rng.permutation(n)

    def queries(self, start: int, count: int) -> np.ndarray:
        return self.X[self.order[np.arange(start, start + count)
                                 % len(self.order)]]


def weights(seed: int, config: dict) -> dict:
    """w ~ N(0, 1/features), float64: X w then spans all three pieces of
    the sigmoid."""
    d = config["features"]
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(d, 1) / np.sqrt(d)}


def feed(seed: int, config: dict) -> Gaussian:
    return Gaussian(seed, config["features"])


def program(rt, X, params: dict, config: dict) -> np.ndarray:
    """One batch of secure predictions on the party runtime: the weights
    and the queries are shared, the probabilities opened."""
    from repro.nn.runtime_engine import RuntimeEngine
    from repro.train import paper_ml as PML
    eng = RuntimeEngine(rt)
    sh = {"w": eng.from_plain(params["w"])}
    return np.asarray(eng.to_plain(
        PML.reg_predict(eng, sh, eng.from_plain(X), logistic=True)))


@jax.jit
def _predict(w, X):
    return jnp.clip(X @ w + 0.5, 0.0, 1.0)


def reference(params: dict, X, config: dict) -> np.ndarray:
    """The plain float32 forward at full float32 matmul precision."""
    with jax.default_matmul_precision("highest"):
        p = _predict(jnp.asarray(params["w"], jnp.float32),
                     jnp.asarray(X, jnp.float32))
    return np.asarray(p, np.float64)


def numbers(params: dict, queries, outputs, config: dict) -> dict:
    """prob_gap: the largest |probability - reference probability| over
    every query the window answered."""
    want = reference(params, queries, config)
    return {"prob_gap": float(np.max(np.abs(
        np.asarray(outputs).reshape(want.shape) - want)))}


def ops_per_sample(config: dict) -> int:
    return 2 * config["features"]


def rehearsal(config: dict, traffic: dict) -> tuple[dict, dict]:
    return config, dict(traffic, batch=min(traffic["batch"], REHEARSE_BATCH))
