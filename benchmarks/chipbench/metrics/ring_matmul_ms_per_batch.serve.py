"""Device time of the jitted ring contraction (``core/ring.py``
``_ring_matmul``, XLA module ``jit__ring_matmul``) per batch of the traced
serving window, dealer and online side together, in ms."""


def read(run):
    if run["driver"] != "serve" or run["trace"] is None or not run["batches"]:
        return None
    s = sum(v for k, v in run["trace"]["modules"].items()
            if "_ring_matmul" in k)
    if s == 0:
        return None
    return 1e3 * s / run["batches"]
