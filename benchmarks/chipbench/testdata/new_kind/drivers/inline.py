"""inline: a fixed number of batches, one after another, each through the
model's program on a fresh ``FourPartyRuntime`` that deals its own
preprocessing inline: no server, no dealer thread.

A fixture of ``test_new_kind.py``: a second driver kind that joins the
benchmark as new files only, with an end-to-end metric of its own
(``inline_preds_per_s``) and a ``layer`` without ``batches``.  The traffic
gives ``batch`` and ``batches``.
"""
from __future__ import annotations

import time

import numpy as np

from common import Cell, Outcome, annotate, memory_peak, sub_seed


def run(cell: Cell) -> Outcome:
    from repro.runtime.runtime import FourPartyRuntime

    model, config = cell.model, cell.config
    batch, n = cell.traffic["batch"], cell.traffic["batches"]
    feed = model.feed(sub_seed(cell.seed, "data"), config)
    params = model.weights(sub_seed(cell.seed, "weights"), config)
    aborted = False

    def one(X):
        nonlocal aborted
        rt = FourPartyRuntime(cell.ring, seed=sub_seed(cell.seed, "program"))
        preds = np.asarray(model.program(rt, X, params, config))
        aborted = aborted or bool(rt.abort_flag())
        return preds

    with annotate("inline.warmup"):
        one(feed.queries(0, batch))
    queries = feed.queries(batch, n * batch)
    with cell.window:
        t0 = time.perf_counter()
        preds = [one(queries[i:i + batch])
                 for i in range(0, len(queries), batch)]
        window_s = time.perf_counter() - t0
    outputs = np.concatenate(preds)
    attempted = len(queries)
    failed = attempted if aborted else attempted - len(outputs)
    return Outcome(
        setup_s=t0 - cell.clock_start, window_s=window_s,
        attempted=attempted, failed=failed,
        numbers=model.numbers(params, queries, outputs, config),
        memory_peak_bytes=memory_peak(),
        end_to_end={"inline_preds_per_s": len(outputs) / window_s},
        layer={"driver": "inline", "samples": len(outputs),
               "window_s": window_s})


def faults() -> dict:
    """``answer_altered``: one opened answer of each batch altered where
    the engine opens it."""

    def answer_altered():
        from repro.nn.runtime_engine import RuntimeEngine
        original = RuntimeEngine.to_plain

        def broken(self, x):
            out = np.array(original(self, x))
            out.flat[0] += 0.1
            return out

        return RuntimeEngine, "to_plain", broken

    return {"answer_altered": answer_altered}
