"""Batched secure prediction served by the party-sliced runtime.

The twin of serve/engine.py's ``PredictionServer``: same submit/flush
batching, but each batch executes across four ``Party`` instances over a
measured transport -- so the reported network numbers are *measured* wire
traffic (per directed link, per phase), not analytic tallies.  Running both
servers on the same model is the end-to-end cross-check of the paper's
cost lemmas at serving scale (benchmarks/runtime_smoke.py does exactly
that and asserts the two agree).

Transport backends:

  * default -- a fresh in-memory ``LocalTransport`` per batch (a real
    deployment provisions fresh offline material the same way);
  * ``net_model=`` -- wraps each batch's transport in a
    ``NetModelTransport``, adding modeled per-phase wall-clock under the
    given LAN/WAN link profile to the report;
  * ``serve_over_sockets`` -- the distributed path: four long-lived party
    daemons over TCP serve the stream batch by batch, returning
    predictions plus measured per-link wire traffic and (optionally)
    modeled time.

Offline/online split (repro.offline):

  * ``PartyPredictionServer(prep="pipelined")`` -- a background dealer
    streams one PrepStore per batch into a bounded queue; each batch then
    executes **online-only** (zero offline bytes, transport-enforced), so
    the reported online wall-clock is a true serving latency;
  * ``serve_over_sockets(prep="ahead")`` (legacy ``prep_ahead=True``) --
    deals one session per batch up front, serializes the bank to disk,
    and the party daemons load it ONCE at startup; every batch task runs
    online-only over the real TCP mesh;
  * ``serve_over_sockets(prep="live")`` -- no whole-stream dealing: a
    ``DealerDaemon`` streams batch k's session into the RUNNING daemons
    over the cluster control channel while batch k-1 is served, so
    serving starts immediately, the stream could be open-ended, and the
    mesh still carries zero offline bytes (transport-enforced).
"""
from __future__ import annotations

import dataclasses
import functools
import tempfile
from typing import Callable

import numpy as np

from .. import obs
from ..core.costs import LAN, WAN, NetworkModel
from ..core.ring import RING64
from ..runtime import FourPartyRuntime, LocalTransport
from .engine import form_batches
from .gateway import LocalMember, ServingGateway, record_serve_metrics

# runtime.net (sockets, cluster spawn, network model) is imported lazily
# inside the paths that need it, keeping the in-process serving path free
# of socket machinery -- the same invariant runtime/__init__.py keeps.


@dataclasses.dataclass
class PartyServeStats:
    batches: int = 0
    queries: int = 0
    online_rounds: int = 0
    online_bits: int = 0
    offline_bits: int = 0
    compute_s: float = 0.0
    online_compute_s: float = 0.0      # online-only wall (prep modes)
    online_compute_cpu_s: float = 0.0  # the online thread's CPU time in it
    offline_deal_s: float = 0.0        # dealer wall (overlapped: pipelined)
    offline_deal_cpu_s: float = 0.0    # the dealer thread's CPU time in it
    prep_wait_s: float = 0.0           # online side blocked on the dealer
    modeled_s: dict = dataclasses.field(
        default_factory=lambda: {"offline": 0.0, "online": 0.0})
    link_online_bits: dict = dataclasses.field(default_factory=dict)
    aborted: bool = False

    def add_transport(self, tp) -> None:
        t = tp.totals()
        self.online_rounds += t["online"]["rounds"]
        self.online_bits += t["online"]["bits"]
        self.offline_bits += t["offline"]["bits"]
        for link, bits in tp.per_link().items():
            acc = self.link_online_bits.setdefault(link, 0)
            self.link_online_bits[link] = acc + bits["online"]

    def latency(self, net: NetworkModel) -> float:
        if self.batches == 0:
            return 0.0
        return net.seconds(self.online_rounds / self.batches,
                           self.online_bits / self.batches)


# one serve-layer metrics implementation for every path (the gateway's
# collectors call it per completed dispatch); the old name stays as an
# alias for callers that imported it from here
_record_serve_metrics = record_serve_metrics


class PartyPredictionServer:
    """predict_fn(rt, X_batch) -> np.ndarray predictions; a fresh
    FourPartyRuntime (fresh PRF counters + transport) per batch, as a real
    deployment would provision fresh offline material.

    ``net_model`` (a runtime.net.NetModel) adds per-link modeled
    wall-clock to the report alongside the coarse LAN/WAN estimates.

    ``prep="pipelined"`` runs the offline-online split: a background
    dealer (repro.offline.PrepPipeline) produces one PrepStore per batch
    while batches execute online-only from the stores -- offline work
    leaves the serving critical path, and the report's
    ``online_only_ms_per_batch`` is wall-clock with zero offline bytes.
    """

    def __init__(self, predict_fn: Callable, batch_size: int = 32,
                 ring=RING64, seed: int = 0, net_model=None,
                 prep: str | None = None, prep_capacity: int = 2):
        assert prep in (None, "pipelined"), prep
        self.predict_fn = predict_fn
        self.batch_size = batch_size
        self.ring = ring
        self.seed = seed
        self.net_model = net_model
        self.prep = prep
        self.prep_capacity = prep_capacity
        self.stats = PartyServeStats()
        self._queue: list[np.ndarray] = []
        self._batches_dealt = 0
        # the serve-layer dispatch machinery is the gateway's; this
        # server is its single-member in-process degenerate case
        self._pipe = None
        self._gw: ServingGateway | None = None

    def _gateway(self) -> ServingGateway:
        if self._gw is None:
            self._gw = ServingGateway(
                members=[LocalMember(self._run_batch)],
                max_batch=self.batch_size, max_wait_ms=None,
                ring=self.ring)
        return self._gw

    def submit(self, x: np.ndarray) -> None:
        self._queue.append(np.asarray(x))

    def close(self) -> None:
        """Stop the dispatch machinery (idle daemon threads otherwise)."""
        if self._gw is not None:
            self._gw.close()
            self._gw = None

    # -- per-batch transports ---------------------------------------------
    def _transport(self):
        base = LocalTransport()
        if self.net_model is not None:
            from ..runtime.net import NetModelTransport
            return base, NetModelTransport(base, self.net_model)
        return base, base

    def _account(self, base, tp, rt) -> None:
        self.stats.batches += 1
        self.stats.add_transport(base)
        if self.net_model is not None:
            for phase in ("offline", "online"):
                self.stats.modeled_s[phase] += tp.seconds(phase)
        self.stats.aborted = self.stats.aborted or bool(rt.abort_flag())

    # -- one batch, either path (runs inside the gateway's collector) -------
    def _run_batch(self, X, n):
        if self._pipe is not None:
            return self._run_batch_pipelined(X, n)
        base, tp = self._transport()
        rt = FourPartyRuntime(self.ring, seed=self.seed, transport=tp)
        with obs.timed(self.stats, "compute_s", span="serve.batch",
                       queries=n):
            preds = np.asarray(self.predict_fn(rt, X))
        self.stats.queries += n
        self._account(base, tp, rt)
        return preds

    def _run_batch_pipelined(self, X, n):
        from ..offline import OnlinePrep
        waited = self.stats.prep_wait_s
        with obs.timed(self.stats, "prep_wait_s", span="prep.wait",
                       cat="prep") as sp:
            session, store, drep = self._pipe.next_store()
            sp.set(session=session)
        obs.get_registry().counter(
            "trident_prep_wait_us_total",
            "wall-clock blocked acquiring prep material (us)").inc(
            (self.stats.prep_wait_s - waited) * 1e6)
        self.stats.offline_deal_s += drep.wall_s
        self.stats.offline_deal_cpu_s += drep.cpu_s
        base, tp = self._transport()
        tp.forbid_phase("offline")
        rt = FourPartyRuntime(self.ring, transport=tp,
                              prep=OnlinePrep(store))
        with obs.timed(self.stats, "online_compute_s", "compute_s",
                       cpu="online_compute_cpu_s",
                       span="serve.batch.online", queries=n):
            preds = np.asarray(self.predict_fn(rt, X))
        self.stats.queries += n
        self._account(base, tp, rt)
        assert base.totals()["offline"]["bits"] == 0
        return preds

    def _deal_program(self, X, rt):
        self.predict_fn(rt, X)

    def _drain(self, batches: list) -> list:
        """Route the formed batches through the gateway (single
        ``LocalMember`` pool) and gather predictions in order."""
        gw = self._gateway()
        futs = [gw.submit_batch(X, n=n) for X, n in batches]
        out: list = []
        for (_X, n), fut in zip(batches, futs):
            out.extend(np.asarray(fut.result().preds)[:n])
        return out

    def flush(self) -> list:
        batches = form_batches(self._queue, self.batch_size)
        if self.prep != "pipelined":
            return self._drain(batches)
        from ..offline import PrepPipeline
        base_seed = self.seed + self._batches_dealt
        self._batches_dealt += len(batches)
        programs = [functools.partial(self._deal_program, np.zeros_like(X))
                    for X, _ in batches]
        with PrepPipeline(programs, ring=self.ring, base_seed=base_seed,
                          capacity=self.prep_capacity) as pipe:
            self._pipe = pipe
            try:
                return self._drain(batches)
            finally:
                self._pipe = None

    def report(self) -> dict:
        links = {f"P{a}->P{b}": bits for (a, b), bits
                 in sorted(self.stats.link_online_bits.items())}
        nb = max(self.stats.batches, 1)
        out = {
            "queries": self.stats.queries,
            "batches": self.stats.batches,
            "aborted": self.stats.aborted,
            "online_rounds_per_batch": self.stats.online_rounds / nb,
            "online_bits_per_batch": self.stats.online_bits / nb,
            "offline_bits_per_batch": self.stats.offline_bits / nb,
            "lan_latency_ms": self.stats.latency(LAN) * 1e3,
            "wan_latency_s": self.stats.latency(WAN),
            "link_online_bits": links,
        }
        if self.net_model is not None:
            out[f"modeled_{self.net_model.name}_online_s_per_batch"] = \
                self.stats.modeled_s["online"] / nb
            out[f"modeled_{self.net_model.name}_offline_s_per_batch"] = \
                self.stats.modeled_s["offline"] / nb
        if self.prep == "pipelined":
            out["online_only_ms_per_batch"] = \
                self.stats.online_compute_s / nb * 1e3
            out["offline_deal_s_per_batch"] = \
                self.stats.offline_deal_s / nb
        return out


# ---------------------------------------------------------------------------
# Distributed serving: four long-lived party daemons over TCP.
# ---------------------------------------------------------------------------
def _serve_batch(rt, _rank, predict_fn=None, X=None):
    """Party-daemon task: one batch through predict_fn on this runtime."""
    return np.asarray(predict_fn(rt, X))


def _zero_deal_program(predict_fn, X, rt):
    """Module-level deal twin of ``_serve_batch`` (shapes only)."""
    predict_fn(rt, np.zeros_like(X))


def _serve_program_for_step(step, *, predict_fn, batches):
    """Picklable ``step -> deal program`` for the live dealer daemon:
    session k is batch k's offline material (shapes only)."""
    return functools.partial(_zero_deal_program, predict_fn, batches[step])


def serve_over_sockets(predict_fn: Callable, queries, batch_size: int = 32,
                       ring=RING64, seed: int = 0, net_model=None,
                       timeout: float = 300.0, cluster=None,
                       prep: str | None = None,
                       prep_ahead: bool = False,
                       prep_dir: str | None = None,
                       live_ahead: int = 2,
                       metrics: bool = False):
    """Serve a query stream across four party processes over TCP.

    ``predict_fn(rt, X_batch)`` has the same contract as
    ``PartyPredictionServer``'s: a module-level (picklable, since the
    party processes are spawned) callable returning the prediction
    *array* for the batch -- reconstruct inside and return one party's
    opened copy, as examples/secure_inference_parties.py does.  Returns
    (predictions list, report dict); the report carries the measured
    per-link wire traffic all four processes agree on.

    Batches are submitted as tasks to a ``PartyCluster`` of **long-lived
    daemons** (mesh built once, reused across batches); pass ``cluster=``
    to reuse one you manage across multiple streams.

    Prep modes (``prep=``):

      * ``"ahead"`` (legacy spelling ``prep_ahead=True``) -- the offline
        phase for EVERY batch is dealt up front (``repro.offline``),
        serialized to ``prep_dir`` (default: a temp dir), loaded by the
        daemons once at startup, and each batch task runs **online-only**
        -- the daemons' transports forbid offline-phase sends, and the
        report's totals show zero offline bytes;
      * ``"live"`` -- no whole-stream dealing: the daemons start with an
        EMPTY live bank and a ``DealerDaemon`` streams batch k's session
        over the control channel while batch k-1 is served, bounded by
        ``live_ahead`` look-ahead.  Same online-only/zero-offline-bytes
        contract on the mesh, but serving starts immediately and the
        stream could be open-ended.

    ``metrics=True`` starts an HTTP metrics exporter in every daemon (and
    the dealer), scrapes them once at end of stream, and puts the merged
    cluster health document in the report under ``"health"``
    (docs/OBSERVABILITY.md).
    """
    from ..runtime.net.cluster import PartyCluster

    if prep_ahead:
        if prep not in (None, "ahead"):
            raise ValueError(
                f"prep_ahead=True (legacy spelling of prep='ahead') "
                f"conflicts with prep={prep!r}")
        prep = "ahead"
    assert prep in (None, "ahead", "live"), prep

    queries = [np.asarray(q) for q in queries]
    batches = [np.stack(queries[i:i + batch_size])
               for i in range(0, len(queries), batch_size)]

    own_cluster = cluster is None
    if not own_cluster:
        # the daemons execute under the CLUSTER's configuration; reject
        # conflicting arguments instead of silently mislabeling results
        if cluster.ring is not ring:
            raise ValueError("cluster= was built for a different ring")
        if net_model is not cluster.net_model:
            raise ValueError(
                "net_model mismatch: pass the model to PartyCluster (the "
                "daemons integrate the clock), not to serve_over_sockets")
    if prep is not None and not own_cluster:
        raise ValueError(f"prep={prep!r} needs to provision its own "
                         "cluster (daemons load or stream the bank)")
    prep_path = None
    deal_wall = 0.0
    if prep == "ahead":
        from ..offline import deal_sessions
        with obs.stopwatch() as sw:
            bank, _ = deal_sessions(
                [functools.partial(_zero_deal_program, predict_fn, X)
                 for X in batches],
                ring=ring, base_seed=seed)
            prep_path = prep_dir or tempfile.mkdtemp(prefix="prepbank-")
            bank.save(prep_path)
        deal_wall = sw.s
    if own_cluster:
        cluster = PartyCluster(ring=ring, timeout=timeout,
                               net_model=net_model, prep_path=prep_path,
                               live_prep=(prep == "live"),
                               live_ahead=live_ahead, metrics=metrics)
    dealer = None
    try:
        if prep == "live":
            from ..offline.live import DealerDaemon
            # the dealer is data-independent: ship SHAPES (zeros), not the
            # query stream, into the dealer process
            dealer = DealerDaemon(
                cluster,
                functools.partial(_serve_program_for_step,
                                  predict_fn=predict_fn,
                                  batches=[np.zeros_like(X)
                                           for X in batches]),
                ring=ring, base_seed=seed, ahead=live_ahead,
                total=len(batches))
        preds: list = []
        totals = {p: {"rounds": 0, "bits": 0}
                  for p in ("offline", "online")}
        link_online: dict = {}
        aborted = False
        wall = 0.0
        modeled = None
        # the dispatch/accounting machinery is the gateway's (the
        # single-cluster degenerate pool); batches stay sequential here
        # -- submit, wait, submit -- so cluster.task_walls keep their
        # per-batch round-trip meaning for the netbench measurements
        gw = ServingGateway(predict_fn, clusters=[cluster],
                            max_batch=batch_size, max_wait_ms=None,
                            ring=ring, base_seed=seed, timeout=timeout)
        try:
            for k, X in enumerate(batches):
                fut = gw.submit_batch(
                    X, seed=seed + k,
                    prep="bank" if prep is not None else None,
                    prep_session=k if prep is not None else None,
                    timeout=timeout)
                br = fut.result(timeout=timeout + 60.0)
                results = br.results
                ref = results[0]
                aborted = aborted or br.abort
                preds.extend(np.asarray(results[1].result))
                for p in totals:
                    for kk in totals[p]:
                        totals[p][kk] += ref.totals[p][kk]
                for link, bits in ref.per_link.items():
                    link_online[link] = link_online.get(link, 0) \
                        + bits["online"]
                wall += max(r.wall_s for r in results)
                if ref.modeled_s is not None:
                    modeled = modeled or {p: 0.0 for p in ref.modeled_s}
                    for p, s in ref.modeled_s.items():
                        modeled[p] += s
        finally:
            gw.close()
        report = {
            "queries": len(queries),
            "batches": len(batches),
            "aborted": aborted,
            "totals": totals,
            "link_online_bits": {f"P{a}->P{b}": bits for (a, b), bits
                                 in sorted(link_online.items())},
            "party_wall_s": wall,
            "cluster_tasks": cluster.tasks_run,
        }
        if prep is not None:
            report["online_only"] = True
            report["prep"] = prep
            assert totals["offline"]["bits"] == 0, totals
        if prep == "ahead":
            report["offline_deal_s"] = deal_wall
            report["prep_path"] = prep_path
        if prep == "live":
            report["live_sessions_streamed"] = dealer.dealt
        if modeled is not None and net_model is not None:
            report[f"modeled_{net_model.name}_s"] = modeled
        if getattr(cluster, "metrics", False):
            # scrape while the daemons (and dealer) are still up: the
            # health doc is part of the stream's report
            report["health"] = cluster.health(dealer=dealer)
        return preds, report
    finally:
        if dealer is not None:
            dealer.close()
        if own_cluster:
            cluster.close()
