"""The general traffic generator.  A traffic file names it (``"driver"``)
and gives its parameters; a configuration file gives the network and its
ring.  The driver builds the program's objects from the run's seed, warms
up (set-up), runs the timed window, and then compares what the window
produced with the plain reference.

  serve   one client streams batches of queries through one ``flush()`` of
          ``PartyPredictionServer(prep="pipelined")``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import sys
import time
import traceback

import numpy as np

import compare
import data
import reference

KERNEL_LAUNCHES = "trident_kernel_launches_total"


@dataclasses.dataclass
class Cell:
    """What a driver needs: the configuration, the traffic, the run."""
    config: dict
    traffic: dict
    seed: int
    seconds: float
    ring: object                          # the program's Ring to run on
    window: contextlib.AbstractContextManager  # wraps the timed window
    clock_start: float                    # perf_counter at process start

    @property
    def dims(self) -> tuple:
        return (self.config["features"], *self.config["layers"])


@dataclasses.dataclass
class Outcome:
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    numbers: dict                         # compared with the limits
    memory_peak_bytes: int | None
    end_to_end: dict                      # name -> value
    layer: dict                           # what per-layer readers read


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory_peak() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _net(cell: Cell):
    from repro.train import paper_ml as PML
    return PML.MLPNet(features=cell.config["features"],
                      layers=tuple(cell.config["layers"]))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _predict(rt, X, *, params, net):
    """One batch of secure predictions on the party runtime: the weights
    and the queries are shared, the probabilities opened."""
    from repro.nn.runtime_engine import RuntimeEngine
    from repro.train import paper_ml as PML
    eng = RuntimeEngine(rt)
    sh = {k: eng.from_plain(params[k]) for k in sorted(params)}
    return np.asarray(eng.to_plain(
        PML.mlp_net_predict(eng, sh, net, eng.from_plain(X))))


def _stats(srv) -> dict:
    s = srv.stats
    return {"batches": s.batches, "queries": s.queries,
            "online_rounds": s.online_rounds,
            "online_compute_s": s.online_compute_s,
            "offline_deal_s": s.offline_deal_s, "aborted": s.aborted}


def serve(cell: Cell) -> Outcome:
    from repro.obs.registry import get_registry
    from repro.serve.party_server import PartyPredictionServer

    batch = cell.traffic["batch"]
    feed = data.MNISTLike(data.sub_seed(cell.seed, "data"),
                          features=cell.config["features"],
                          classes=cell.config["layers"][-1])
    params = data.mlp_net_init(data.sub_seed(cell.seed, "weights"), cell.dims)
    srv = PartyPredictionServer(
        functools.partial(_predict, params=params, net=_net(cell)),
        batch_size=batch, ring=cell.ring,
        seed=data.sub_seed(cell.seed, "program"), prep="pipelined",
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
    probs = np.stack(preds) if preds else np.zeros((0,))
    attempted = len(queries)
    answered = len(preds)
    failed = attempted if end["aborted"] else attempted - answered
    numbers = (compare.serve_numbers(params, queries, probs)
               if answered == attempted else {"score_gap": math.inf})
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
               "flops_per_sample": reference.forward_flops(cell.dims)})


DRIVERS = {"serve": serve}
