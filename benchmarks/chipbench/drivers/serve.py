"""serve: one client streams batches of queries through one ``flush()`` of
``PartyPredictionServer(prep="pipelined")``, the dealer dealing each
batch's preprocessing beside the online phase of the one before.

The traffic gives ``batch``, ``prep_capacity``, and the stream's length
as ``batch_s`` (its nominal cost of a batch) and ``min_batches``.  The
model (``Cell.model``) gives the weights, the queries, one batch on the
program's path and the comparison with its plain reference.
"""
from __future__ import annotations

import functools
import time
import traceback

import numpy as np

from common import Cell, Outcome, annotate, log, memory_peak, sub_seed

KERNEL_LAUNCHES = "trident_kernel_launches_total"


def _stats(srv) -> dict:
    s = srv.stats
    return {"batches": s.batches, "queries": s.queries,
            "online_rounds": s.online_rounds,
            "online_compute_s": s.online_compute_s,
            "offline_deal_s": s.offline_deal_s, "aborted": s.aborted}


def run(cell: Cell) -> Outcome:
    from repro.obs.registry import get_registry
    from repro.serve.party_server import PartyPredictionServer

    model, config = cell.model, cell.config
    batch = cell.traffic["batch"]
    feed = model.feed(sub_seed(cell.seed, "data"), config)
    params = model.weights(sub_seed(cell.seed, "weights"), config)
    srv = PartyPredictionServer(
        functools.partial(model.program, params=params, config=config),
        batch_size=batch, ring=cell.ring,
        seed=sub_seed(cell.seed, "program"), prep="pipelined",
        prep_capacity=cell.traffic["prep_capacity"])
    try:
        for q in feed.queries(0, batch):
            srv.submit(q)
        with annotate("serve.warmup"):
            srv.flush()
        warm = _stats(srv)
        # the stream's length is fixed by the traffic: as many batches as
        # last about --seconds at the mix's nominal cost of a batch, the
        # same for every seed and every run
        n = max(cell.traffic["min_batches"],
                round(cell.seconds / cell.traffic["batch_s"]))
        log(f"serve.warmup: deal {warm['offline_deal_s']:.3f} s, online "
            f"{warm['online_compute_s']:.3f} s; window stream {n} batches")
        queries = feed.queries(batch, n * batch)
        for q in queries:
            srv.submit(q)
        reg = get_registry()
        launches0 = reg.total(KERNEL_LAUNCHES)
        with cell.window:
            t0 = time.perf_counter()
            try:
                with annotate("serve.flush"):
                    preds = srv.flush()
            except Exception:           # a stream that raises has failed
                log(traceback.format_exc())
                preds = []
            window_s = time.perf_counter() - t0
        launches = reg.total(KERNEL_LAUNCHES) - launches0
        setup_s = t0 - cell.clock_start
        peak = memory_peak()
        end = _stats(srv)
        log(f"serve.flush: {n} batches in {window_s:.3f} s")
    finally:
        srv.close()
    d = {k: end[k] - warm[k] for k in end if k != "aborted"}
    nb = d["batches"] or 1
    attempted = len(queries)
    answered = len(preds)
    failed = attempted if end["aborted"] else attempted - answered
    # a stream that did not answer every query has nothing to compare:
    # each of its limits reads as missed
    numbers = (model.numbers(params, queries, np.stack(preds), config)
               if answered == attempted else {})
    return Outcome(
        setup_s=setup_s, window_s=window_s, attempted=attempted,
        failed=failed, numbers=numbers, memory_peak_bytes=peak,
        end_to_end={
            "serve_preds_per_s": answered / window_s,
            "serve_online_ms_per_batch": d["online_compute_s"] / nb * 1e3},
        layer={"driver": "serve", "batches": nb, "samples": answered,
               "window_s": window_s,
               "online_rounds_per_batch": d["online_rounds"] / nb,
               "dealer_s_per_batch": d["offline_deal_s"] / nb,
               "kernel_launches": launches,
               "flops_per_sample": model.ops_per_sample(config)})


def faults() -> dict:
    """The faults a serving cell can have, each planted underneath the
    timed path: ``name -> plant()``, which gives ``(owner, attribute,
    replacement)`` for ``monkeypatch.setattr``."""

    def answers(fault):
        def plant():
            from repro.serve import party_server as PS
            original = PS.PartyPredictionServer._run_batch_pipelined

            def broken(self, X, n):
                preds = np.array(original(self, X, n))
                if fault == "half_batch":
                    preds[n // 2:] = 0.0    # half of the answers left out
                else:
                    preds[0, 0] += 0.1      # one answer altered where produced
                return preds

            return PS.PartyPredictionServer, "_run_batch_pipelined", broken
        return plant

    return {"half_batch": answers("half_batch"),
            "answer_altered": answers("answer_altered")}
