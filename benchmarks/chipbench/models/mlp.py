"""The paper's MLP networks (``"model": "mlp"``): ``features`` inputs,
ReLU hidden layers of ``layers[:-1]`` and the paper's smx output over
``layers[-1]`` classes.

The weights and the queries are copies of the program's ``mlp_net_init``
and ``MNISTLike`` (``src/repro/train/paper_ml.py``,
``src/repro/train/data.py``), kept here so that a change to the program
cannot move what the benchmark feeds it.  The plain reference follows the
math of the program's ``PlainEngine`` (``nn/engine.py``) without importing
it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

REHEARSE_WIDTH = 16
REHEARSE_BATCH = 16


def _dims(config: dict) -> tuple:
    return (config["features"], *config["layers"])


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


def weights(seed: int, config: dict) -> dict:
    """The program's ``mlp_net_init``: w_i ~ N(0, 1/dims[i]), float64."""
    dims = _dims(config)
    rng = np.random.RandomState(seed)
    return {f"w{i}": (rng.randn(dims[i], dims[i + 1])
                      / np.sqrt(dims[i])).astype(np.float64)
            for i in range(len(dims) - 1)}


def feed(seed: int, config: dict) -> MNISTLike:
    return MNISTLike(seed, features=config["features"],
                     classes=config["layers"][-1])


def program(rt, X, params: dict, config: dict) -> np.ndarray:
    """One batch of secure predictions on the party runtime: the weights
    and the queries are shared, the probabilities opened."""
    from repro.nn.runtime_engine import RuntimeEngine
    from repro.train import paper_ml as PML
    net = PML.MLPNet(features=config["features"],
                     layers=tuple(config["layers"]))
    eng = RuntimeEngine(rt)
    sh = {k: eng.from_plain(params[k]) for k in sorted(params)}
    return np.asarray(eng.to_plain(
        PML.mlp_net_predict(eng, sh, net, eng.from_plain(X))))


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


def reference(params: dict, X, config: dict) -> tuple:
    """The plain float32 forward: (probabilities, each query's smx
    denominator).  Every matmul runs at full float32 precision (a TPU
    otherwise passes float32 matmuls through bfloat16)."""
    f32 = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        p, s = _predict(f32, jnp.asarray(X, jnp.float32))
    return np.asarray(p, np.float64), np.asarray(s, np.float64)


def numbers(params: dict, queries, outputs, config: dict) -> dict:
    """score_gap: the largest |probability - reference probability| over
    every class of every query the window answered, each times that
    query's reference smx denominator ``sum relu(z) + 1e-2``.  The smx
    divides every score by that denominator, which can be as small as
    1e-2, so the bare probability gap of a query whose scores are all near
    0 is its scores' fixed-point rounding magnified up to 100 times; the
    product undoes that and reads the error in the scores' own units."""
    want, denominator = reference(params, queries, config)
    gap = np.abs(np.asarray(outputs) - want)
    return {"score_gap": float(np.max(gap * denominator))}


def ops_per_sample(config: dict) -> int:
    """Multiply-adds of one sample's forward pass, counted as 2 operations
    each: 2 x (number of weights)."""
    dims = _dims(config)
    return 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def rehearsal(config: dict, traffic: dict) -> tuple[dict, dict]:
    """The cell cut to a CPU rehearsal: hidden widths and batch 16."""
    layers = config["layers"]
    config = dict(config, layers=[min(w, REHEARSE_WIDTH)
                                  for w in layers[:-1]] + [layers[-1]])
    return config, dict(traffic, batch=min(traffic["batch"], REHEARSE_BATCH))
