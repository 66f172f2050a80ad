"""Network benchmark: measured wire traffic + modeled LAN/WAN wall-clock
per ML block on the party-sliced runtime -- end-to-end AND online-only.

Each block runs three ways:

  * interleaved (the classic path): one run over a LocalTransport wrapped
    in two stacked ``NetModelTransport``s (LAN inner, WAN outer -- the
    model layer composes, so one run integrates both clocks), reporting
    measured bytes/rounds per phase (== the analytic CostTally) and
    modeled end-to-end wall-clock per phase;
  * prep-ahead dealer (repro.offline.deal): the offline half alone, priced
    under the same stacked models (``*_offline_prep_ms``);
  * online-only executor (repro.offline.run_online): the online half
    alone, from the dealer's PrepStore, with offline-phase sends forbidden
    on the transport -- ``lan_online_only_ms`` / ``wan_online_only_ms``
    are the numbers directly comparable to the paper's online-phase
    benchmark tables, printed next to end-to-end.  The bench asserts the
    split is exact: online-only bytes/rounds == the interleaved run's
    online phase, zero offline bytes, and (for the NN block) bit-identical
    predictions.

``--socket`` adds the 4-process backends: the end-to-end NN block over
TCP, and the **pipelined** NN block -- every party process runs a
background dealer (bounded-queue PrepPipeline) while its online consumer
drains the stores over the real socket mesh -- reporting measured
``online_only_ms`` wall-clock next to the modeled LAN/WAN times.

``--live`` adds the **live-streamed** 4-process training block: the
cluster's PrepBank starts EMPTY and a ``DealerDaemon`` process streams
step k's session over the per-rank control channel while step k-1 runs
online; the block reports measured ``live_online_only_ms`` per step and
asserts bit-identity with the interleaved trajectory plus zero offline
bytes on the mesh.

The TRAINING blocks (on by default; ``--train-only`` for the CI train
job) put one full secure-SGD step -- logreg and the paper's
784-128-128-10 NN, fwd + bwd + update on the RuntimeEngine -- through the
same three-way harness, so ``lan/wan_online_only_ms`` is the measured
per-step online time of distributed training with prep dealt ahead, with
the same exact-split and bit-identity assertions vs the interleaved step.

Every record carries a **compute-vs-wire breakdown**: measured
``local_compute_offline_ms`` / ``local_compute_online_ms`` (the wall-clock
of the party-local math in the phase-isolated dealer / online-only runs)
printed next to the modeled LAN/WAN wire times, plus the
``kernel_backend`` that produced it.  The MLP-inference and both
training-step blocks run TWICE -- kernel_backend="jnp" and "pallas"
(docs/KERNELS.md) -- with outputs and wire costs asserted bit-identical,
so the two breakdowns isolate what the fused kernels change: local
compute only, never bytes or rounds.

``--trace`` turns on the observability plane (docs/OBSERVABILITY.md) for
the socket/live blocks: every party daemon and the dealer record span
traces, the bench adds **measured-vs-modeled attribution** to each
socket record (``measured_online_ms`` from the wire-round spans,
``model_residual_ms`` = measured - modeled), and writes the merged
Chrome trace-event timeline to ``--trace-out`` (open in ui.perfetto.dev;
smoke-checked in CI by scripts/check_trace.py).

``--metrics`` exercises the LIVE metrics plane (the always-on
``MetricsRegistry`` + per-daemon HTTP exporters): every in-process block
asserts the registry's per-link byte counters equal ``per_link()``
exactly, every socket block asserts the same over the daemons'
``PartyResult.metrics`` snapshots, each BENCH record embeds a compact
``metrics`` summary, and the ``--live`` block runs a ``HealthMonitor``
scraping all five exporters (4 ranks + dealer) MID-TRAINING, writing the
merged cluster health doc to ``--health-out`` (gated in CI by
scripts/check_health.py; regressions vs the committed baseline by
scripts/bench_compare.py).

``--serving`` (or ``--serving-only``, the CI serve job) adds the
SERVING-GATEWAY block: a 2-cluster ``ServingGateway`` pool with dynamic
batching against the single-cluster sequential baseline, under a
saturation burst and an offered-load sweep paced at multiples of the
measured sequential QPS -- reporting achieved QPS, p50/p95/p99 latency,
QPS at the p95 SLO, batching efficiency, and per-member utilization,
and asserting the >= 3x speedup bar, per-dispatch bit-identity to the
joint sim, and (``--metrics``) per-member registry-vs-transport byte
equality.

One ``BENCH {json}`` line per block on stdout; the aggregate goes to
``--out`` (default netbench.json) for CI artifact upload.

    PYTHONPATH=src python -m benchmarks.netbench [--quick] [--socket]
        [--live] [--trace [--trace-out trace.json]]
        [--metrics [--health-out health.json]] [--serving]
"""
import argparse
import json
import math
import os
import sys
import time
from collections import defaultdict

import numpy as np

from repro import obs
from repro.obs import health as obs_health
from repro.core.ring import RING64
from repro.offline import OnlinePrep, PrepPipeline, deal, run_online
from repro.runtime import FourPartyRuntime, LocalTransport
from repro.runtime import activations as RA
from repro.runtime import protocols as RT
from repro.runtime.net import LAN, WAN, NetModelTransport, run_four_parties
from repro.runtime.net.cluster import PartyCluster

_rng = np.random.RandomState(0)
_SOCK_W1 = _rng.randn(8, 6) * 0.4
_SOCK_W2 = _rng.randn(6, 3) * 0.4
_SOCK_X = _rng.randn(4, 8)
_SOCK_SEED = 7
_SOCK_SESSIONS = 3

_SERVE_W = np.random.RandomState(3).randn(6, 4) * 0.4
_SERVE_FEATURES = 6
# the p95 SLO is 6x the pooled gateway's measured single-query latency
# floor (a warm padded-batch dispatch on an otherwise-idle pool; under
# load concurrent members contend for CPU, so the multiplier leaves
# room for that), and the offered-load sweep paces at these multiples
# of the sequential-baseline QPS -- self-normalizing, so the block
# means the same thing on fast and slow runners (absolute per-dispatch
# latency varies severalfold across CI)
_SERVE_SLO_X = 6.0
# the 0.5x point is deliberately under capacity on every runner (the
# padded-batch dispatch is slower than a sequential 1-row one, and
# concurrent members contend for CPU), so qps_at_slo is non-degenerate
_SERVE_SWEEP_X = (0.5, 1.0, 3.0, 8.0)


def _mkparent(path):
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)


def _enc(x):
    return RING64.encode(np.asarray(x))


def _mlp(rt, X, W1, W2):
    xs = RT.share(rt, _enc(X))
    w1 = RT.share(rt, _enc(W1))
    w2 = RT.share(rt, _enc(W2))
    h = RA.relu(rt, RT.matmul_tr(rt, xs, w1))
    out = RA.sigmoid(rt, RT.matmul_tr(rt, h, w2))
    return RT.reconstruct(rt, out)


def _socket_nn_program(rt, rank):
    """Module-level so the spawned party processes can import it."""
    opened = _mlp(rt, _SOCK_X, _SOCK_W1, _SOCK_W2)
    return np.asarray(opened[rank])


def _sock_deal_program(rt):
    """Offline twin of _socket_nn_program: shapes only (zeros)."""
    _mlp(rt, np.zeros_like(_SOCK_X), _SOCK_W1, _SOCK_W2)


def _socket_pipelined_program(rt, rank):
    """Pipelined offline/online over the real mesh: a background dealer
    thread (LocalTransport, deterministic -- every process derives the
    identical per-party material) streams PrepStores into a bounded
    queue; the online consumer drains them over the socket mesh, which
    forbids offline traffic for the span of each online run."""
    base = rt.transport
    lan_tp = NetModelTransport(base, LAN)
    wan_tp = NetModelTransport(lan_tp, WAN)
    outs = []
    online_wall = 0.0
    deal_wall = 0.0
    programs = [_sock_deal_program] * _SOCK_SESSIONS
    with PrepPipeline(programs, ring=rt.ring,
                      base_seed=_SOCK_SEED) as pipe:
        for _k, store, drep in pipe.stores():
            deal_wall += drep.wall_s
            base.forbid_phase("offline")
            try:
                ort = FourPartyRuntime(rt.ring, transport=wan_tp,
                                       prep=OnlinePrep(store))
                t0 = time.perf_counter()
                opened = _mlp(ort, _SOCK_X, _SOCK_W1, _SOCK_W2)
                online_wall += time.perf_counter() - t0
            finally:
                base.allow_phase("offline")
            outs.append(np.asarray(opened[rank]))
    return {
        "out": outs,
        "online_wall_s": online_wall,
        "deal_wall_s": deal_wall,
        "lan_online_s": lan_tp.seconds("online"),
        "wan_online_s": wan_tp.seconds("online"),
    }


def _train_blocks(quick: bool):
    """Training-step blocks: one full secure-SGD step (fwd + bwd + update,
    params revealed) per program -- logreg and the paper's 784-128-128-10
    NN -- run through the same three-way harness as the inference blocks,
    so the BENCH JSON carries measured per-step ``lan/wan_online_only_ms``
    with the exact-split assertions vs the interleaved step."""
    from repro.train import data as D
    from repro.train import secure_sgd as SGD

    b = 4 if quick else 8
    d = 16 if quick else 64
    logreg = SGD.logreg_task(features=d, lr=0.5)
    logreg_params = logreg.init_params(seed=0)
    logreg_batch = D.RegressionData(features=d, n=256, seed=1,
                                    logistic=True).batch(0, b)
    nn = SGD.nn_task(lr=0.5)            # 784-128-128-10
    nn_params = nn.init_params(seed=0)
    nn_batch = D.MNISTLike(n=256, seed=2).batch(0, b)[:2]

    def step_fn(task, params, batch):
        def fn(rt):
            new, _loss, _ = SGD.step_program(task, params, batch)(rt)
            return np.concatenate(
                [np.asarray(new[k]).ravel() for k in sorted(new)])
        return fn

    return [
        (f"train_logreg_step_d{d}_b{b}",
         step_fn(logreg, logreg_params, logreg_batch)),
        (f"train_nn_step_784-128-128-10_b{b}",
         step_fn(nn, nn_params, nn_batch)),
    ]


def _blocks(quick: bool):
    rng = np.random.RandomState(0)
    b, d_in, d_hid, d_out = (8, 32, 16, 10) if quick else (32, 128, 64, 10)
    X = rng.randn(b, d_in)
    W = rng.randn(d_in, d_hid) * 0.2
    W2 = rng.randn(d_hid, d_out) * 0.2
    H = rng.randn(b, d_hid)

    def dense(rt):
        RT.matmul_tr(rt, RT.share(rt, _enc(X)), RT.share(rt, _enc(W)))

    def square(rt):
        hs = RT.share(rt, _enc(H))
        RT.mult_tr(rt, hs, hs)

    def relu(rt):
        RA.relu(rt, RT.share(rt, _enc(H)))

    def sigmoid(rt):
        RA.sigmoid(rt, RT.share(rt, _enc(H)))

    def mlp(rt):
        return np.asarray(_mlp(rt, X, W, W2)[1])

    return [
        (f"dense_{d_in}x{d_hid}_b{b}", dense),
        (f"square_act_{b}x{d_hid}", square),
        (f"relu_{b}x{d_hid}", relu),
        (f"sigmoid_{b}x{d_hid}", sigmoid),
        (f"mlp_inference_{d_in}-{d_hid}-{d_out}_b{b}", mlp),
    ]


def _stacked():
    lan_tp = NetModelTransport(LocalTransport(), LAN)
    wan_tp = NetModelTransport(lan_tp, WAN)  # models stack: one run, 2 clocks
    return lan_tp, wan_tp


def _nonzero_links(per_link) -> dict:
    """``per_link()`` restricted to its non-zero cells -- the exact shape
    ``MetricsRegistry.link_bits()`` reports (counters only exist for links
    that carried bytes)."""
    out = {}
    for link, per in per_link.items():
        cell = {ph: b for ph, b in per.items() if b}
        if cell:
            out[link] = cell
    return out


def _metrics_summary(snap) -> dict:
    """Compact registry totals embedded per BENCH record (--metrics)."""
    return {
        "wire_bits": obs.snapshot_total(snap, "trident_wire_bits_total"),
        "wire_msgs": obs.snapshot_total(snap, "trident_wire_msgs_total"),
        "round_scopes": obs.snapshot_total(
            snap, "trident_wire_round_scopes_total"),
        "protocol_calls": obs.snapshot_total(
            snap, "trident_protocol_calls_total"),
        "kernel_launches": obs.snapshot_total(
            snap, "trident_kernel_launches_total"),
    }


def run_block(name, fn, seed=0, kernel_backend="jnp",
              metrics: bool = False) -> tuple:
    """Returns (rec, interleaved_out).  ``kernel_backend`` routes every
    party's local compute ("jnp" or "pallas" -- bit-identical, so all the
    exact-split/wire assertions hold unchanged in both modes); the rec's
    ``local_compute_{offline,online}_ms`` are the measured per-phase local
    compute wall-clock of the split runs, printed next to the modeled
    LAN/WAN wire times -- the compute-vs-wire breakdown.

    ``metrics=True`` runs the registry-vs-transport contract in process:
    a fresh ``MetricsRegistry`` is installed before each sub-run's
    transports are built (they capture the registry at construction), the
    registry's per-link byte counters are asserted EQUAL to ``per_link()``
    after the run, and the rec carries a compact ``metrics`` summary."""
    prev_reg = obs.install_registry(obs.MetricsRegistry(
        f"netbench-{name}")) if metrics else None
    try:
        return _run_block_inner(name, fn, seed, kernel_backend, metrics)
    finally:
        if metrics:
            obs.install_registry(prev_reg)


def _run_block_inner(name, fn, seed, kernel_backend, metrics) -> tuple:
    # ---- interleaved end-to-end ------------------------------------------
    lan_tp, wan_tp = _stacked()
    rt = FourPartyRuntime(RING64, seed=seed, transport=wan_tp,
                          kernel_backend=kernel_backend)
    t0 = time.perf_counter()
    interleaved_out = fn(rt)
    compute_s = time.perf_counter() - t0
    totals = rt.transport.totals()
    if metrics:
        # the always-on registry saw every byte the transport measured
        reg = obs.get_registry()
        assert reg.link_bits() == _nonzero_links(rt.transport.per_link()), \
            (name, reg.link_bits(), rt.transport.per_link())
        interleaved_metrics = _metrics_summary(reg.snapshot())
        # fresh registry for the split runs below: their transports are
        # new constructions, so their counters start from zero too
        obs.install_registry(obs.MetricsRegistry(f"netbench-{name}-split"))
    on_r = totals["online"]["rounds"]
    rec = {
        "bench": "netbench",
        "block": name,
        "kernel_backend": kernel_backend,
        "offline_rounds": totals["offline"]["rounds"],
        "offline_bits": totals["offline"]["bits"],
        "online_rounds": on_r,
        "online_bits": totals["online"]["bits"],
        "lan_offline_s": lan_tp.seconds("offline"),
        "lan_online_s": lan_tp.seconds("online"),
        "wan_offline_s": wan_tp.seconds("offline"),
        "wan_online_s": wan_tp.seconds("online"),
        "wan_online_round_frac":
            (on_r * WAN.default.rtt_s / wan_tp.seconds("online"))
            if wan_tp.seconds("online") else 0.0,
        "compute_s": compute_s,
        "aborted": bool(rt.abort_flag()),
    }
    assert not rec["aborted"], f"{name}: honest run aborted"

    # ---- offline/online split: dealer, then the online-only executor -----
    rt_kw = {"kernel_backend": kernel_backend}
    lan_d, wan_d = _stacked()
    store, drep = deal(fn, ring=RING64, seed=seed, transport=wan_d,
                       runtime_kwargs=rt_kw)
    lan_o, wan_o = _stacked()
    online_out, orep = run_online(fn, store, ring=RING64, transport=wan_o,
                                  runtime_kwargs=rt_kw)
    if metrics:
        # the split registry accumulated BOTH split transports (deal +
        # online-only): its counters must equal their merged per-link view
        merged = _nonzero_links(wan_d.per_link())
        for link, per in _nonzero_links(wan_o.per_link()).items():
            cell = merged.setdefault(link, {})
            for ph, b in per.items():
                cell[ph] = cell.get(ph, 0) + b
        assert obs.get_registry().link_bits() == merged, \
            (name, obs.get_registry().link_bits(), merged)

    # the split must be exact: same online wire cost, zero offline bytes,
    # and the same modeled online clock the interleaved run integrated
    assert (orep.online_rounds, orep.online_bits) == \
        (on_r, totals["online"]["bits"]), (orep, totals)
    assert orep.offline_bits == 0
    assert (drep.offline_rounds, drep.offline_bits) == \
        (totals["offline"]["rounds"], totals["offline"]["bits"])
    assert math.isclose(wan_o.seconds("online"), wan_tp.seconds("online"),
                        rel_tol=1e-9)
    if interleaved_out is not None:
        assert np.array_equal(np.asarray(interleaved_out),
                              np.asarray(online_out)), \
            f"{name}: online-only result diverged"

    rec.update({
        "prep_entries": drep.entries,
        "offline_deal_wall_s": drep.wall_s,
        "lan_offline_prep_ms": lan_d.seconds("offline") * 1e3,
        "wan_offline_prep_ms": wan_d.seconds("offline") * 1e3,
        "lan_online_only_ms": lan_o.seconds("online") * 1e3,
        "wan_online_only_ms": wan_o.seconds("online") * 1e3,
        "online_only_wall_s": orep.wall_s,
        # compute-vs-wire: measured local compute per phase (the split
        # runs isolate each phase), next to the modeled wire times above
        "local_compute_offline_ms": drep.wall_s * 1e3,
        "local_compute_online_ms": orep.wall_s * 1e3,
    })
    if metrics:
        rec["metrics"] = interleaved_metrics
    return rec, interleaved_out


def _measured_phase_ms(chunks) -> dict:
    """Per-rank traced wall-clock inside wire-round scopes: {rank: {phase:
    ms}}.  The max over ranks is the measured cost of the synchronized
    round structure -- the number the NetModel predicts."""
    per = defaultdict(lambda: defaultdict(float))
    for c in chunks:
        for ev in c["events"]:
            if ev["ph"] == "X" and ev["cat"] == "wire.round":
                per[c["rank"]][ev["args"]["phase"]] += ev["dur"] * 1e3
    return {rank: dict(ms) for rank, ms in per.items()}


def _assert_metrics_consistent(per_task_results, strict: bool = True) -> None:
    """The registry's per-link bytes over the real socket mesh: every
    rank's CUMULATIVE registry byte counters (the final task's
    ``PartyResult.metrics`` snapshot) must equal the sum of its per-task
    ``per_link()`` deltas EXACTLY.  ``strict=False`` confines the
    check to the online phase, for programs that also run process-local
    transports (the pipelined block's in-daemon dealer counts its local
    deals on the same daemon registry, off the mesh)."""
    by_rank = defaultdict(list)
    for results in per_task_results:
        for r in results:
            by_rank[r.rank].append(r)
    for rank, rs in sorted(by_rank.items()):
        snap = rs[-1].metrics
        assert snap is not None, f"P{rank}: no metrics snapshot"
        got = obs.snapshot_link_bits(snap)
        want: dict = defaultdict(lambda: defaultdict(int))
        for r in rs:
            for link, per in r.per_link.items():
                for phase, bits in per.items():
                    if bits:
                        want[link][phase] += bits
        # every byte the transport measured is on a registry counter
        for link, per in want.items():
            for phase, bits in per.items():
                assert got.get(link, {}).get(phase) == bits, \
                    (rank, link, phase, bits, got)
        phases = ("offline", "online") if strict else ("online",)
        for phase in phases:
            got_total = sum(per.get(phase, 0) for per in got.values())
            want_total = sum(per.get(phase, 0) for per in want.values())
            assert got_total == want_total, \
                (rank, phase, got_total, want_total)


def _attribution(rec, results, modeled_online_s, sessions=1) -> list:
    """The measured-vs-modeled pass: fold the ranks' traced round wall
    time into the record next to the NetModel prediction.  Returns the
    trace chunks for the caller's merged timeline."""
    chunks = [r.trace for r in results]
    per = _measured_phase_ms(chunks)
    measured = max(p.get("online", 0.0) for p in per.values()) / sessions
    modeled = modeled_online_s / sessions * 1e3
    rec.update({
        "measured_online_ms": measured,
        "measured_offline_ms":
            max(p.get("offline", 0.0) for p in per.values()) / sessions,
        # measured minus modeled: >0 means real socket rounds cost more
        # than the model's rtt+bits/bandwidth account (scheduling, copies,
        # GIL); <0 means the model over-prices this deployment
        "model_residual_ms": measured - modeled,
        "trace_events": sum(len(c["events"]) for c in chunks),
    })
    return chunks


def run_socket_block(timeout: float = 300.0, trace: bool = False,
                     metrics: bool = False) -> tuple:
    t0 = time.perf_counter()
    with PartyCluster(timeout=timeout, net_model=WAN,
                      trace=trace, metrics=metrics) as cluster:
        results = cluster.submit(_socket_nn_program, seed=_SOCK_SEED,
                                 timeout=timeout)
        trace = cluster.trace           # may have come from TRIDENT_TRACE
        metrics = cluster.metrics       # may have come from TRIDENT_METRICS
        if metrics:
            _assert_metrics_consistent([results])
            health = cluster.health()
            assert health["healthy"], health["probes"]
    wall = time.perf_counter() - t0
    ref = results[0]
    assert all(r.totals == ref.totals for r in results)
    assert not any(r.abort for r in results)
    totals = ref.totals
    rec = {
        "bench": "netbench",
        "block": "mlp_inference_socket_4proc",
        "offline_rounds": totals["offline"]["rounds"],
        "offline_bits": totals["offline"]["bits"],
        "online_rounds": totals["online"]["rounds"],
        "online_bits": totals["online"]["bits"],
        "wan_offline_s": ref.modeled_s["offline"],
        "wan_online_s": ref.modeled_s["online"],
        "frames_sent": sum(ref.frames_sent.values()),
        "party_wall_s": max(r.wall_s for r in results),
        "launch_wall_s": wall,
        "aborted": False,
    }
    if metrics:
        rec["metrics"] = _metrics_summary(results[0].metrics)
    chunks = _attribution(rec, results, ref.modeled_s["online"]) \
        if trace else []
    return rec, chunks


def run_socket_pipelined_block(timeout: float = 300.0,
                               trace: bool = False,
                               metrics: bool = False) -> tuple:
    """The pipelined 4-process backend: background dealers + online-only
    consumers over the real TCP mesh; ``online_only_ms`` is measured
    per-batch online wall-clock (max over parties)."""
    t0 = time.perf_counter()
    with PartyCluster(timeout=timeout, trace=trace,
                      metrics=metrics) as cluster:
        results = cluster.submit(_socket_pipelined_program,
                                 seed=_SOCK_SEED, timeout=timeout)
        trace = cluster.trace
        metrics = cluster.metrics
        if metrics:
            # strict=False: the in-daemon dealers count their local deal
            # traffic on the same registry, off the mesh
            _assert_metrics_consistent([results], strict=False)
    wall = time.perf_counter() - t0
    ref = results[0]
    assert all(r.totals == ref.totals for r in results)
    assert not any(r.abort for r in results)
    # the mesh carried ONLY online traffic (dealing is process-local)
    assert ref.totals["offline"]["bits"] == 0, ref.totals
    # every session must reproduce its interleaved twin (session k is
    # dealt from seed _SOCK_SEED + k) bit-for-bit, at every party
    for k in range(_SOCK_SESSIONS):
        local = FourPartyRuntime(RING64, seed=_SOCK_SEED + k)
        want = np.asarray(_mlp(local, _SOCK_X, _SOCK_W1, _SOCK_W2)[1])
        for res in results:
            assert np.array_equal(res.result["out"][k], want), \
                f"pipelined online diverged (session {k}, P{res.rank})"
    n = _SOCK_SESSIONS
    rec = {
        "bench": "netbench",
        "block": "mlp_inference_socket_4proc_pipelined",
        "sessions": n,
        "online_rounds": ref.totals["online"]["rounds"] // n,
        "online_bits": ref.totals["online"]["bits"] // n,
        "offline_bits_on_mesh": ref.totals["offline"]["bits"],
        "online_only_ms":
            max(r.result["online_wall_s"] for r in results) / n * 1e3,
        "offline_deal_ms_overlapped":
            max(r.result["deal_wall_s"] for r in results) / n * 1e3,
        "lan_online_only_ms": float(ref.result["lan_online_s"]) / n * 1e3,
        "wan_online_only_ms": float(ref.result["wan_online_s"]) / n * 1e3,
        "party_wall_s": max(r.wall_s for r in results),
        "launch_wall_s": wall,
        "aborted": False,
    }
    if metrics:
        rec["metrics"] = _metrics_summary(results[0].metrics)
    chunks = _attribution(rec, results,
                          float(ref.result["wan_online_s"]),
                          sessions=n) if trace else []
    return rec, chunks


def run_socket_live_block(timeout: float = 300.0, steps: int = 3,
                          trace: bool = False,
                          metrics: bool = False) -> tuple:
    """The live-streamed 4-process training backend: the cluster's
    PrepBank starts EMPTY and a ``DealerDaemon`` streams step k's session
    over the per-rank control channel while step k-1 runs online.  The
    block asserts the acceptance contract -- bit-identity with the
    interleaved (joint-simulation) trajectory and ZERO offline bytes on
    the TCP mesh -- and reports measured per-step online wall-clock
    (``live_online_only_ms``: steady-state steps, where the stream has
    overlapped the previous step; step 0 additionally pays the daemons'
    JIT warmup and is reported separately as ``first_step_ms``.  The wait
    for a not-yet-streamed session happens before the measured span, so
    the per-step numbers are pure online execution)."""
    from repro.runtime.net.cluster import PartyCluster
    from repro.train import data as D
    from repro.train import secure_sgd as SGD

    batch, seed = 8, _SOCK_SEED
    task = SGD.logreg_task(features=6, lr=0.5)
    data = D.RegressionData(features=6, n=256, seed=1, logistic=True)
    params0 = task.init_params(seed=0)

    # the interleaved reference trajectory (the tri-world contract makes
    # joint == interleaved runtime == cluster, asserted in the test suite)
    ref_p, ref = dict(params0), []
    for step in range(steps):
        ref_p, loss, _ = SGD.run_step(task, ref_p, data.batch(step, batch),
                                      step=step, base_seed=seed,
                                      world="joint")
        ref.append((dict(ref_p), loss))

    t0 = time.perf_counter()
    health = None
    with PartyCluster(live_prep=True, timeout=timeout,
                      trace=trace, metrics=metrics) as cluster:
        with SGD.attach_live_dealer(cluster, task, params0,
                                    data.batch(0, batch), base_seed=seed,
                                    ahead=2, total=steps) as dealer:
            metrics = cluster.metrics
            # scrape all five exporters (4 ranks + dealer) MID-RUN: the
            # monitor polls while training steps execute, and a probe that
            # fires at any point fails the final health doc
            monitor = obs_health.HealthMonitor(
                cluster, dealer=dealer, interval=0.2) if metrics else None
            sgd = SGD.ClusterSGD(cluster, task, base_seed=seed,
                                 prep="live", dealer=dealer)
            p = dict(params0)
            for step in range(steps):
                p, loss, abort = sgd.step_fn(p, step,
                                             *data.batch(step, batch))
                assert not abort
                # bit-identity vs the interleaved run, every step
                assert loss == ref[step][1], (step, loss, ref[step][1])
                for k in p:
                    assert np.array_equal(p[k], ref[step][0][k]), (step, k)
            offline_bits = sgd.offline_bits_on_mesh()
            results = sgd.results
            if metrics:
                _assert_metrics_consistent(results)
                health = monitor.stop()
        # party chunks per step + the dealer's per-session chunks: the
        # merged timeline shows deal(k) overlapping online step k-1
        chunks = ([*cluster.trace_chunks, *dealer.trace_chunks]
                  if cluster.trace else [])
    wall = time.perf_counter() - t0
    assert offline_bits == 0, offline_bits   # transport-enforced
    per_step_ms = [max(r.wall_s for r in res) * 1e3 for res in results]
    steady = per_step_ms[1:] or per_step_ms
    step1 = results[min(1, steps - 1)][0]
    rec = {
        "bench": "netbench",
        "block": "train_logreg_live_socket_4proc",
        "steps": steps,
        "offline_bits_on_mesh": offline_bits,
        "online_rounds_per_step": step1.totals["online"]["rounds"],
        "online_bits_per_step": step1.totals["online"]["bits"],
        "live_online_only_ms": sum(steady) / len(steady),
        "first_step_ms": per_step_ms[0],
        "per_step_ms": per_step_ms,
        "launch_wall_s": wall,
        "bit_identical": True,
        "aborted": False,
    }
    if metrics:
        rec["metrics"] = _metrics_summary(results[-1][0].metrics)
    if chunks:
        labels = {c["label"] for c in chunks}
        assert "dealer" in labels, labels     # the dealer made the timeline
        per = _measured_phase_ms([c for c in chunks
                                  if c.get("rank") is not None])
        rec.update({
            "measured_online_ms":
                max(p.get("online", 0.0) for p in per.values()) / steps,
            "prep_wait_ms_total": max(
                sum(r.prep_wait_s for r in res) for res in zip(*results))
                * 1e3,
            "trace_events": sum(len(c["events"]) for c in chunks),
        })
    return rec, chunks, health


def _serve_predict(rt, Xb):
    """Serving-gateway predict_fn (module-level: daemons are spawned):
    share -> linear -> relu -> open P1's copy."""
    xs = RT.share(rt, _enc(Xb))
    w = RT.share(rt, _enc(_SERVE_W))
    out = RA.relu(rt, RT.matmul_tr(rt, xs, w))
    return RING64.decode(RT.reconstruct(rt, out)[1])


def _serve_joint(Xb, seed):
    """The joint-simulation twin of ``_serve_predict``: the bit-identity
    reference for every dispatched (padded batch, seed)."""
    from repro.core import activations as ACT
    from repro.core import protocols as PR
    from repro.core.context import make_context
    ctx = make_context(RING64, seed=seed)
    xs = PR.share(ctx, _enc(Xb))
    w = PR.share(ctx, _enc(_SERVE_W))
    out = ACT.relu(ctx, PR.matmul_tr(ctx, xs, w))
    return RING64.decode(np.asarray(PR.reconstruct(ctx, out)))


def _serve_check_gateway(gw, metrics: bool) -> int:
    """The serving acceptance contract, per pool member: every dispatched
    batch's predictions are bit-identical to the joint sim of the (padded
    batch, seed) it was dispatched with, and (``--metrics``) every
    member's cumulative registry byte counters equal the sum of its
    per-task transport deltas EXACTLY.  Returns the dispatch count."""
    n = 0
    for m in gw._members:
        assert len(m.dispatch_log) == len(m.results_log), \
            (m.idx, len(m.dispatch_log), len(m.results_log))
        for rec, results in zip(m.dispatch_log, m.results_log):
            want = _serve_joint(rec["X"], rec["seed"])
            got = np.asarray(results[1].result)
            assert np.array_equal(got, want), \
                f"member {m.idx}: dispatch diverged from joint sim"
            n += 1
        if metrics and m.results_log:
            _assert_metrics_consistent(m.results_log)
    return n


def _serve_point(gw, queries, timeout: float,
                 rate_qps: float | None = None) -> dict:
    """One offered-load point: submit ``queries`` (paced at ``rate_qps``,
    or as fast as possible when None), drain, and report this point's
    achieved QPS / latency percentiles / batching efficiency from the
    gateway meter's deltas."""
    from repro.serve.gateway import _pct
    meter = gw.meter
    with meter._lock:
        n0, q0, b0 = len(meter.query_lat_s), meter.queries, meter.batches
    t0 = time.perf_counter()
    for i, q in enumerate(queries):
        gw.submit(q)
        if rate_qps:
            delay = t0 + (i + 1) / rate_qps - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
    gw.drain(timeout=timeout)
    wall = time.perf_counter() - t0
    with meter._lock:
        lats = sorted(meter.query_lat_s[n0:])
        nq, nb = meter.queries - q0, meter.batches - b0
    assert nq == len(queries), (nq, len(queries))
    return {
        "offered_qps": rate_qps,
        "queries": nq,
        "achieved_qps": nq / wall,
        "avg_batch_size": nq / max(nb, 1),
        "p50_ms": _pct(lats, 50) * 1e3,
        "p95_ms": _pct(lats, 95) * 1e3,
        "p99_ms": _pct(lats, 99) * 1e3,
    }


def run_serving_block(timeout: float = 300.0, metrics: bool = False,
                      pool: int = 2, max_batch: int = 8) -> dict:
    """The serving-gateway block: a single-cluster SEQUENTIAL baseline
    (pool=1, max_batch=1, one query in flight at a time -- the classic
    blocking-submit serve loop) against a ``pool``-cluster gateway with
    dynamic batching, under a saturation burst and a small offered-load
    sweep paced at ``_SERVE_SWEEP_X`` multiples of the sequential QPS.
    Reports achieved QPS, p50/p95/p99 latency, QPS at the p95 SLO
    (``_SERVE_SLO_X`` times the pooled gateway's own single-query
    latency floor -- a padded ``max_batch``-row dispatch, measured
    warm), batching efficiency, and per-member utilization; asserts
    the >= 3x QPS acceptance bar and the per-member bit-identity /
    registry-consistency contracts."""
    from repro.serve.gateway import ServingGateway

    rng = np.random.RandomState(11)
    qdim = _SERVE_FEATURES

    # -- single-cluster sequential baseline --------------------------------
    with ServingGateway(_serve_predict, pool=1, max_batch=1,
                        max_wait_ms=None, base_seed=101, timeout=timeout,
                        metrics=metrics, keep_results=True) as base_gw:
        base_gw.submit(rng.randn(qdim)).result(timeout=timeout)  # JIT warm
        n_seq = 8
        t0 = time.perf_counter()
        for q in rng.randn(n_seq, qdim):
            base_gw.submit(q).result(timeout=timeout)   # one in flight
        seq_wall = time.perf_counter() - t0
        checked = _serve_check_gateway(base_gw, metrics)
        assert checked == n_seq + 1, checked
    sequential_qps = n_seq / seq_wall

    # -- pooled gateway with dynamic batching ------------------------------
    with ServingGateway(_serve_predict, pool=pool, max_batch=max_batch,
                        max_wait_ms=5.0, base_seed=7, timeout=timeout,
                        metrics=metrics, keep_results=True) as gw:
        # warm every member's compiled batch shape (least-loaded placement
        # spreads the back-to-back full batches across the pool)
        warm = _serve_point(gw, rng.randn(pool * max_batch, qdim), timeout)
        # the pooled latency floor: one warm singleton dispatch (every
        # pooled dispatch pads to max_batch rows, so this -- not the
        # 1-row sequential baseline -- is the p95 SLO's natural anchor)
        t1 = time.perf_counter()
        gw.submit(rng.randn(qdim)).result(timeout=timeout)
        slo_ms = _SERVE_SLO_X * (time.perf_counter() - t1) * 1e3
        # saturation burst: offered >> capacity, the batching headline
        burst = _serve_point(gw, rng.randn(6 * max_batch, qdim), timeout)
        # offered-load sweep: paced arrivals, latency vs load, offered
        # rates scaled to the measured sequential capacity
        sweep = [_serve_point(gw, rng.randn(3 * max_batch, qdim), timeout,
                              rate_qps=x * sequential_qps)
                 for x in _SERVE_SWEEP_X]
        _serve_check_gateway(gw, metrics)
        rep = gw.report()
        assert not rep["aborted"] and rep["evictions"] == 0, rep
    pooled_qps = burst["achieved_qps"]
    speedup = pooled_qps / sequential_qps
    under_slo = [p["achieved_qps"] for p in sweep
                 if p["p95_ms"] <= slo_ms]
    rec = {
        "bench": "netbench",
        "block": "serving_gateway",
        "pool": pool,
        "max_batch": max_batch,
        "slo_ms": slo_ms,
        "queries": warm["queries"] + burst["queries"]
        + sum(p["queries"] for p in sweep) + n_seq + 2,   # +2: both warms
        "sequential_qps": sequential_qps,
        "pooled_qps": pooled_qps,
        "batching_speedup_x": speedup,
        "qps_at_slo": max(under_slo) if under_slo else 0.0,
        "avg_batch_size": burst["avg_batch_size"],
        "p50_ms": burst["p50_ms"],
        "p95_ms": burst["p95_ms"],
        "p99_ms": burst["p99_ms"],
        "sweep": sweep,
        "per_member_utilization": {
            mid: per["utilization"]
            for mid, per in rep["per_member"].items()},
        "evictions": rep["evictions"],
        "bit_identical": True,
        "aborted": False,
    }
    # the acceptance bar: batching + pooling is a real throughput win
    assert speedup >= 3.0, rec
    return rec


def run(quick: bool = True, socket: bool = False, out: str | None = None,
        timeout: float = 300.0, train: bool = True,
        train_only: bool = False, live: bool = False,
        trace: bool = False, trace_out: str | None = None,
        metrics: bool = False, health_out: str | None = None,
        serving: bool = False, serving_only: bool = False):
    records = []
    trace = trace or obs.tracing_enabled()
    metrics = metrics or obs.metrics_enabled()
    trace_chunks: list = []
    print("netbench: measured wire traffic + modeled LAN/WAN wall-clock "
          "(end-to-end AND online-only)")
    print(f"  LAN preset: rtt {LAN.default.rtt_s*1e3:.2f} ms, "
          f"{LAN.default.bandwidth_bps/1e9:.0f} Gbps | "
          f"WAN preset: rtt {WAN.default.rtt_s*1e3:.1f} ms, "
          f"{WAN.default.bandwidth_bps/1e6:.0f} Mbps")
    blocks = [] if (train_only or serving_only) else _blocks(quick)
    if (train or train_only) and not serving_only:
        blocks += _train_blocks(quick)
    # blocks that also run on the pallas kernel backend (ISSUE 6 contract:
    # at least the logreg and NN blocks carry the compute-vs-wire
    # breakdown for BOTH backends, with bit-identity asserted)
    both = ("mlp_inference", "train_logreg", "train_nn")
    for name, fn in blocks:
        rec, jout = run_block(name, fn, metrics=metrics)
        records.append(rec)
        print("BENCH " + json.dumps(rec))
        if not any(name.startswith(p) for p in both):
            continue
        prec, pout = run_block(name, fn, kernel_backend="pallas",
                               metrics=metrics)
        # the backends are bit-identical: same outputs, same wire costs
        if jout is not None:
            assert np.array_equal(np.asarray(jout), np.asarray(pout)), \
                f"{name}: pallas backend output diverged from jnp"
        for k in ("offline_rounds", "offline_bits", "online_rounds",
                  "online_bits", "wan_online_s", "prep_entries"):
            assert prec[k] == rec[k], (name, k, prec[k], rec[k])
        records.append(prec)
        print("BENCH " + json.dumps(prec))
    # the paper's WAN observation, asserted: activations round-dominated
    for rec in records:
        if "relu" in rec["block"] or "sigmoid" in rec["block"]:
            assert rec["wan_online_round_frac"] > 0.9, rec
    if socket:
        rec, chunks = run_socket_block(timeout=timeout, trace=trace,
                                       metrics=metrics)
        records.append(rec)
        trace_chunks.extend(chunks)
        print("BENCH " + json.dumps(rec))
        rec, chunks = run_socket_pipelined_block(timeout=timeout,
                                                 trace=trace,
                                                 metrics=metrics)
        records.append(rec)
        trace_chunks.extend(chunks)
        print("BENCH " + json.dumps(rec))
    if live:
        rec, chunks, health = run_socket_live_block(timeout=timeout,
                                                    trace=trace,
                                                    metrics=metrics)
        records.append(rec)
        trace_chunks.extend(chunks)
        print("BENCH " + json.dumps(rec))
        if health is not None:
            # the live block's merged health doc -- every rank + the
            # dealer healthy, no probe ever fired -- is the CI gate
            # (scripts/check_health.py)
            assert health["healthy"], health
            path = health_out or "cluster_health.json"
            _mkparent(path)
            with open(path, "w") as f:
                json.dump(health, f, indent=2)
            print(f"[netbench] wrote cluster health doc to {path} "
                  f"(healthy={health['healthy']}, "
                  f"scrapes={health['scrapes']})")
    if serving or serving_only:
        rec = run_serving_block(timeout=timeout, metrics=metrics)
        records.append(rec)
        print("BENCH " + json.dumps(rec))
    if trace and trace_chunks:
        path = trace_out or "netbench_trace.json"
        doc = obs.write_chrome_trace(path, trace_chunks)
        snap = obs.metrics_snapshot(doc)
        print(f"[netbench] wrote merged trace ({len(doc['traceEvents'])} "
              f"events, processes {sorted(doc['metadata']['processes'])}) "
              f"to {path} -- open in https://ui.perfetto.dev")
        print("TRACE " + json.dumps({"rounds": snap["rounds"],
                                     "sends": snap["sends"]}))
    if out:
        _mkparent(out)
        with open(out, "w") as f:
            json.dump({"bench": "netbench", "quick": quick,
                       "records": records}, f, indent=2)
        print(f"[netbench] wrote {len(records)} records to {out}")
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small block sizes (CI smoke)")
    ap.add_argument("--socket", action="store_true",
                    help="also run the 4-process socket NN blocks "
                         "(end-to-end + pipelined online-only)")
    ap.add_argument("--no-train", dest="train", action="store_false",
                    help="skip the secure-SGD training-step blocks")
    ap.add_argument("--train-only", action="store_true",
                    help="run ONLY the training-step blocks (CI train job)")
    ap.add_argument("--live", action="store_true",
                    help="also run the live-streamed 4-process training "
                         "block (empty bank, DealerDaemon over the "
                         "cluster control channel)")
    ap.add_argument("--trace", action="store_true",
                    help="trace the socket/live blocks (TRIDENT_TRACE=1 "
                         "equivalent): measured_online_ms + "
                         "model_residual_ms in the BENCH records, merged "
                         "Chrome trace JSON to --trace-out")
    ap.add_argument("--trace-out", default="netbench_trace.json",
                    help="merged Perfetto-viewable trace path (with "
                         "--trace; default netbench_trace.json)")
    ap.add_argument("--metrics", action="store_true",
                    help="live metrics plane (TRIDENT_METRICS=1 "
                         "equivalent): per-daemon HTTP exporters, "
                         "registry-vs-transport byte consistency asserts, "
                         "a compact metrics summary per BENCH record, and "
                         "(with --live) the mid-run cluster health doc "
                         "to --health-out")
    ap.add_argument("--health-out", default="cluster_health.json",
                    help="cluster health doc path (with --metrics --live; "
                         "default cluster_health.json)")
    ap.add_argument("--serving", action="store_true",
                    help="also run the serving-gateway block: 2-cluster "
                         "pool + dynamic batching vs the single-cluster "
                         "sequential baseline, with an offered-load sweep")
    ap.add_argument("--serving-only", action="store_true",
                    help="run ONLY the serving-gateway block (CI serve "
                         "job)")
    ap.add_argument("--out", default="netbench.json")
    ap.add_argument("--timeout", type=float, default=300.0)
    args = ap.parse_args()
    run(quick=args.quick, socket=args.socket, out=args.out,
        timeout=args.timeout, train=args.train, train_only=args.train_only,
        live=args.live, trace=args.trace, trace_out=args.trace_out,
        metrics=args.metrics, health_out=args.health_out,
        serving=args.serving, serving_only=args.serving_only)
    return 0


if __name__ == "__main__":
    sys.exit(main())
