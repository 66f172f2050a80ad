"""Long-lived party daemons on one machine, plus the one-shot launcher.

``PartyCluster`` spawns one OS process per party; each builds its
``SocketTransport`` endpoint of the TCP mesh ONCE (optionally wrapped in a
``NetModelTransport``), optionally loads a serialized ``PrepBank`` at
startup, and then serves **tasks** -- submitted protocol programs -- until
closed.  The mesh, the loaded prep material, and the warm JAX runtime
persist across tasks, so a query stream pays connection setup and store
deserialization once, not per batch (the per-stream spawn cost used to
dominate short streams).

``cluster.submit(program)`` runs ``program(rt, rank)`` in every party
process on a fresh ``FourPartyRuntime`` over the persistent transport and
returns the four ``PartyResult``s; measured traffic/modeled time are
**per-task deltas**, so results compose across a stream.  A task with
``prep="bank"`` consumes the next PrepBank session and executes
online-only: the daemon's transport *forbids* offline traffic for the span
of the task (any offline send raises), realizing the offline/online split
on the real wire.

Live prep streaming (``live_prep=True``): each daemon starts with an
EMPTY ``LivePrepBank`` plus a control thread draining a per-rank
**control queue** -- a multiprocessing channel separate from the TCP
mesh.  A driver-side ``offline.live.DealerDaemon`` deals sessions
continuously and ships each session down control queue i addressed to
daemon i (the daemon stamps it ``party=i`` for error attribution), so
``submit(prep="bank", prep_session=k)`` works for sessions dealt *after*
daemon startup: a task blocks until its session's material arrives
(bounded look-ahead backpressures the dealer), and the mesh still carries
zero offline bytes, transport-enforced.  A dealer failure poisons the
live banks, so a waiting task fails with the dealer's traceback instead
of a generic timeout.

Async dispatch (the serving-gateway seam): ``submit`` is now a thin
wrapper over ``submit_nowait`` (enqueue one task on every daemon, return
a ``TaskHandle`` immediately) plus ``collect`` (gather that task's four
``PartyResult``s).  The daemons serve their task queues strictly in
order, so a driver may keep several tasks in flight on one cluster --
task k+1's submit overlaps task k's execution -- and a pool scheduler
(``serve.gateway``) overlaps submit/collect across whole clusters.
Results come back on one shared queue; ``collect`` routes them into
per-task buckets by task id, so concurrent collectors (one worker thread
per pool member) never steal each other's results.

A failed or timed-out task leaves the lock-step mesh undefined, so the
cluster POISONS itself: the failing ``collect`` raises with the collected
tracebacks, and every later ``submit``/``collect`` raises
``ClusterPoisoned`` immediately (instead of hanging until timeout against
daemons that already exited).  Tear the cluster down and start a fresh
one.

Port allocation: ``_free_ports`` probes free ports by binding and
releasing them, so another process (or a sibling cluster booting
concurrently -- exactly what a gateway pool does) can grab a port in the
window between the probe and the daemon's bind.  Boot therefore
fail-fasts on the first daemon error and retries the whole mesh
construction with fresh ports when the error is ``EADDRINUSE``, up to
``PORT_RETRIES`` attempts.

``run_four_parties(program)`` is the one-shot path (spawn, run one task,
tear down) used by tests and benches; it is now a thin wrapper over a
temporary cluster.

``program`` must be a module-level callable (the processes are spawned, so
it travels by qualified name) and should return numpy-convertible pytrees.

Determinism note: all four processes run the same protocol program from
the same seed, so their PRF streams, message schedules, and measured
tallies agree -- the driver asserts exactly that in tests.  Tamper rules
are installed identically in every process; the process whose rank is the
message's sender corrupts the wire copy, and every process mirrors the
corruption in its local simulation so the replicated state stays
consistent with what actually crossed the network.
"""
from __future__ import annotations

import dataclasses
import logging
import multiprocessing as mp
import os
import queue as _queue
import socket
import threading
import time
import traceback

import numpy as np

from ...core.ring import RING64, Ring
from ...obs import (MetricsRegistry, Tracer, get_registry, get_tracer,
                    install_registry, install_tracer, metrics_enabled,
                    tracing_enabled)

DEFAULT_TIMEOUT = 120.0
DEFAULT_LIVE_AHEAD = 2
PORT_RETRIES = 3

_log = logging.getLogger(__name__)


class ClusterPoisoned(RuntimeError):
    """A previous task failed or timed out, leaving the lock-step mesh in
    an undefined state; the cluster refuses further submits (the daemons
    may already have exited -- a blind retry would hang until timeout).
    Tear the cluster down and spawn a fresh one."""


@dataclasses.dataclass
class PartyResult:
    """One party process's view of one task."""

    rank: int
    result: object
    totals: dict
    per_link: dict
    abort: bool
    wall_s: float
    modeled_s: dict | None = None     # phase -> seconds (when net_model set)
    frames_sent: dict | None = None   # (src, dst) -> wire frames (this task)
    task_id: int | None = None        # correlates results with submissions
    prep_wait_s: float = 0.0          # blocked on prep material (live banks)
    trace: dict | None = None         # this task's trace chunk (trace=True)
    metrics: dict | None = None       # daemon registry snapshot (metrics=True)


@dataclasses.dataclass
class TaskHandle:
    """A submitted-but-not-yet-collected cluster task (``submit_nowait``).
    Pass it to ``PartyCluster.collect`` to gather the four results."""

    task_id: int
    submitted_at: float          # perf_counter at submit (task_walls base)
    timeout: float


def _addr_in_use(text: str) -> bool:
    """Does a collected boot traceback name the bind port race?"""
    return "EADDRINUSE" in text or "Address already in use" in text


def _free_ports(n: int) -> list:
    socks = []
    try:
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _to_np(tree):
    import jax
    return jax.tree_util.tree_map(np.asarray, tree)


def _totals_delta(after: dict, before: dict) -> dict:
    return {p: {k: after[p][k] - before[p][k] for k in after[p]}
            for p in after}


def _run_task(task, *, ring, transport, base, bank, out_q, rank,
              prep_wait: float = DEFAULT_TIMEOUT, metrics: bool = False):
    from .. import FourPartyRuntime

    t_before = base.totals()
    l_before = {k: dict(v) for k, v in base.per_link().items()}
    f_before = dict(base.frames_sent)
    m_before = dict(transport._sec.total) if transport is not base else None

    tracer = get_tracer()
    reg = get_registry()
    reg.counter("trident_cluster_tasks_total",
                "tasks served by this party daemon").inc()
    g_inflight = reg.gauge("trident_cluster_tasks_inflight",
                           "tasks currently executing (0 or 1)")
    g_inflight.set(1)
    t_task0 = time.perf_counter()
    prep = None
    prep_wait_s = 0.0
    try:
        if task.get("prep") == "bank":
            from ...offline.store import OnlinePrep
            if bank is None:
                raise RuntimeError("task wants prep='bank' but the daemon "
                                   "has no PrepBank (load one at startup "
                                   "with prep_path= or stream one with "
                                   "live_prep=True)")
            session = task.get("prep_session")
            t_prep0 = time.perf_counter()
            if getattr(bank, "live", False):
                # live streaming: the session may not have arrived yet --
                # block until the dealer's watermark passes it (a dead
                # dealer raises its traceback here instead of timing out)
                bank.wait_for(session if session is not None
                              else bank.next_session, timeout=prep_wait)
            if session is not None:
                # step-indexed consumption (training): session == step, so
                # a resumed run skips spent sessions and a retried step
                # raises PrepReplayError instead of silently eating wrong
                # material
                bank.seek(session)
            store = bank.next()
            prep_wait_s = time.perf_counter() - t_prep0
            reg.counter("trident_prep_sessions_consumed_total",
                        "PrepStore sessions consumed by tasks").inc()
            reg.counter("trident_prep_wait_us_total",
                        "wall-clock blocked acquiring prep material "
                        "(us)").inc(prep_wait_s * 1e6)
            sess_no = getattr(store, "meta", {}).get("session")
            reg.gauge("trident_prep_next_session",
                      "next prep session this daemon will consume").set(
                bank.next_session if getattr(bank, "live", False)
                else bank._next)
            reg.gauge("trident_live_bank_depth",
                      "unconsumed sessions buffered in the prep "
                      "bank").set(bank.sessions_left)
            if tracer.enabled:
                tracer.raw_span("prep.acquire", "prep", t_prep0,
                                prep_wait_s, session=sess_no)
            store.party = rank          # attribute store errors to P{rank}
            prep = OnlinePrep(store)
            base.forbid_phase("offline")
        try:
            rt = FourPartyRuntime(ring, seed=task["seed"],
                                  transport=transport, prep=prep,
                                  **task["runtime_kwargs"])
            t0 = time.perf_counter()
            result = task["program"](rt, rank)
            wall = time.perf_counter() - t0
        finally:
            if prep is not None:
                base.allow_phase("offline")
    finally:
        # metrics are live even for failing tasks: the inflight gauge
        # drops back and the wall histogram records the attempt, so a
        # health scrape never sees a phantom running task
        g_inflight.set(0)
        reg.histogram("trident_cluster_task_wall_us",
                      "per-task wall clock (us)").observe(
            (time.perf_counter() - t_task0) * 1e6)
    if tracer.enabled:
        tracer.raw_span(f"task#{task['id']}", "cluster.task", t_task0,
                        time.perf_counter() - t_task0, task_id=task["id"],
                        seed=task["seed"], prep=task.get("prep"),
                        session=task.get("prep_session"))

    t_after = base.totals()
    per_link = {}
    for link, bits in base.per_link().items():
        was = l_before.get(link, {p: 0 for p in bits})
        per_link[link] = {p: bits[p] - was[p] for p in bits}
    frames = {k: v - f_before.get(k, 0)
              for k, v in base.frames_sent.items()}
    out_q.put(PartyResult(
        rank=rank,
        result=_to_np(result),
        totals=_totals_delta(t_after, t_before),
        per_link=per_link,
        abort=bool(rt.abort_flag()),
        wall_s=wall,
        modeled_s=({p: transport._sec.total[p] - m_before[p]
                    for p in m_before} if m_before is not None else None),
        frames_sent={k: v for k, v in frames.items() if v},
        task_id=task["id"],
        prep_wait_s=prep_wait_s,
        # per-task trace delta: drain() resets the buffer, so each task's
        # chunk stands alone and the driver concatenates them
        trace=tracer.drain() if tracer.enabled else None,
        # metrics snapshot is CUMULATIVE (registry counters never reset):
        # the driver diffs snapshots or scrapes the exporter for rates
        metrics=reg.snapshot() if metrics else None,
    ))


def _ctrl_loop(ctrl_q, bank, rank):
    """Daemon-side control thread: drain the per-rank control queue into
    the live bank.  Prep appends may block on the bank's bounded
    look-ahead (that is the backpressure propagating to the dealer).  Any
    failure here (a queue corrupted by a dealer killed mid-put, an
    out-of-order stream) poisons the bank, so a waiting task raises the
    cause instead of timing out."""
    import pickle
    tracer = get_tracer()
    reg = get_registry()
    g_depth = reg.gauge("trident_live_bank_depth",
                        "unconsumed sessions buffered in the prep bank")
    g_mark = reg.gauge("trident_live_bank_watermark",
                       "sessions streamed into the live bank so far")
    try:
        while True:
            # CONC005: bounded wait so a dealer killed without posting the
            # sentinel cannot park this thread forever
            try:
                item = ctrl_q.get(timeout=1.0)
            except _queue.Empty:
                continue
            if item is None:
                return
            kind = item[0]
            if kind == "prep":
                _, session, blob = item
                store = pickle.loads(blob)
                store.party = rank      # attribute store errors to P{rank}
                if tracer.enabled:
                    # the append may block on the bounded look-ahead: the
                    # span IS the backpressure wait, the counter the depth
                    with tracer.span("prep.append", "prep",
                                     session=session):
                        bank.append(session, store)
                    tracer.counter("live_bank_depth", len(bank), "prep")
                else:
                    bank.append(session, store)
                g_depth.set(bank.sessions_left)
                g_mark.set(bank.watermark)
            elif kind == "dealer_error":
                bank.fail(item[1])
                return
            elif kind == "dealer_done":
                bank.finish(item[1])
                return
    except BaseException:
        bank.fail(f"P{rank} control thread died:\n"
                  f"{traceback.format_exc()}")


def boot_device(label: str, expect=None):
    """The device this party (or dealer) computes on, checked at boot.

    JAX falls back to the CPU, with only a warning, when it cannot
    initialize the accelerator -- for example because another process
    holds the chip.  A daemon left on the CPU that way refuses to serve:
    the CPU is used only where ``JAX_PLATFORMS`` asks for it.  ``expect``
    is the device a pinned daemon was given."""
    import jax.numpy as jnp
    dev = next(iter(jnp.zeros(()).devices()))
    if expect is not None and dev != expect:
        raise RuntimeError(f"{label} was given {expect} but computes on "
                           f"{dev}")
    asked = os.environ.get("JAX_PLATFORMS", "").split(",")
    if expect is None and dev.platform == "cpu" and "cpu" not in asked:
        raise RuntimeError(
            f"{label} initialized a {dev.platform} backend "
            f"({dev.device_kind}) without JAX_PLATFORMS=cpu: no "
            f"accelerator was available to it; refusing to serve")
    return dev


def describe_device(dev) -> str:
    return f"{dev.platform}:{dev.id} ({dev.device_kind})"


class _PinnedDaemon(threading.Thread):
    """A party daemon as a thread of this process, computing on one
    device: the deployment shape where one process drives every chip of
    a host (only one process may hold a TPU host's chips)."""

    def __init__(self, device, args):
        super().__init__(target=self._main, args=args, daemon=True,
                         name=f"party-P{args[0]}")
        self.device = device

    def _main(self, *args):
        import jax
        with jax.default_device(self.device):
            _daemon_main(*args, device=self.device)

    def terminate(self) -> None:
        """A thread cannot be killed; a hung daemon thread ends with the
        process (it is a daemon thread)."""


def _daemon_main(rank, endpoints, cfg, task_q, ctrl_q, out_q, device=None):
    exporter = None
    try:
        # a daemon process installs its own labeled tracer and registry;
        # pinned daemon threads share their process's
        if device is None:
            # install the labeled tracer BEFORE the transport exists so
            # the mesh's MeasuredTransport captures it (env TRIDENT_TRACE=1
            # also lands here: spawned children inherit the environment)
            if cfg.get("trace") or tracing_enabled():
                install_tracer(Tracer(f"party-P{rank}", rank=rank))
            # the metrics registry is ALWAYS on (cheap counters); install
            # it labeled and BEFORE the transport for the same capture
            # reason.  cfg["metrics"] only decides whether an HTTP
            # exporter serves it.
            install_registry(MetricsRegistry(f"party-P{rank}", rank=rank))
        dev = boot_device(f"party daemon P{rank}", expect=device)
        metrics_port = None
        if cfg.get("metrics"):
            from ...obs.exporter import MetricsExporter
            exporter = MetricsExporter()
            metrics_port = exporter.port

        from .model import NetModelTransport
        from .socket_transport import SocketTransport

        base = SocketTransport(rank, endpoints, timeout=cfg["timeout"],
                               connect_timeout=cfg["timeout"])
        for rule in cfg["tampers"]:
            base.tamper(**rule)
        transport = base
        if cfg["net_model"] is not None:
            transport = NetModelTransport(base, cfg["net_model"])
        bank = None
        if cfg["prep_path"] is not None:
            from ...offline.store import PrepBank
            bank = PrepBank.load(cfg["prep_path"])
        elif cfg["live_prep"]:
            from ...offline.live import LivePrepBank
            bank = LivePrepBank(ahead=cfg["live_ahead"])
            threading.Thread(target=_ctrl_loop, args=(ctrl_q, bank, rank),
                             daemon=True, name=f"ctrl-P{rank}").start()
        out_q.put(("ready", rank, len(bank) if bank is not None else 0,
                   metrics_port, describe_device(dev)))
        while True:
            task = task_q.get()
            if task is None:
                break
            try:
                # the prep wait must expire BEFORE the driver's _collect
                # clock (which started at submit): otherwise a merely-slow
                # dealer surfaces as the generic daemons-timed-out error
                # instead of wait_for's watermark-naming one
                budget = task.get("timeout") or cfg["timeout"]
                _run_task(task, ring=cfg["ring"], transport=transport,
                          base=base, bank=bank, out_q=out_q, rank=rank,
                          prep_wait=max(1.0, 0.75 * budget),
                          metrics=bool(cfg.get("metrics")))
            except BaseException:
                # a failed task leaves the lock-step mesh undefined: report
                # and stop serving (the driver poisons the cluster)
                out_q.put(("error", rank, traceback.format_exc()))
                break
        base.close()
    except BaseException:
        out_q.put(("error", rank, traceback.format_exc()))
    finally:
        if exporter is not None:
            exporter.close()


class PartyCluster:
    """Four long-lived party daemons over a persistent TCP mesh.

    By default each daemon is a spawned process on the device its JAX
    picks (``boot_device`` refuses a silent CPU fallback).  ``devices``
    (four JAX devices) instead runs party i as a thread of this process
    pinned to ``devices[i]``: one process driving every chip of a host,
    which is how four parties get four chips where a chip cannot be
    handed to a child process."""

    def __init__(self, *, ring: Ring = RING64,
                 timeout: float = DEFAULT_TIMEOUT, tampers=(),
                 net_model=None, prep_path: str | None = None,
                 live_prep: bool = False,
                 live_ahead: int = DEFAULT_LIVE_AHEAD,
                 trace: bool = False, metrics: bool = False,
                 devices=None):
        if live_prep and prep_path is not None:
            raise ValueError(
                "live_prep streams into an initially empty bank; "
                "prep_path loads a frozen one at startup -- pick one")
        ctx = mp.get_context("spawn")
        trace = trace or tracing_enabled()
        metrics = metrics or metrics_enabled()
        if devices is not None:
            devices = list(devices)
            if len(devices) != 4:
                raise ValueError(f"devices: one per party (4), got "
                                 f"{len(devices)}")
            if trace or metrics:
                raise ValueError("pinned daemon threads share their "
                                 "process's tracer and registry: trace/"
                                 "metrics need daemon processes")
        cfg = {
            "ring": ring, "timeout": timeout, "tampers": list(tampers),
            "net_model": net_model, "prep_path": prep_path,
            "live_prep": live_prep, "live_ahead": live_ahead,
            "trace": trace, "metrics": metrics,
        }
        self.ring = ring
        self.timeout = timeout
        self.net_model = net_model
        self.live_prep = live_prep
        self.live_ahead = live_ahead
        self.trace = trace
        self.metrics = metrics
        # rank -> exporter HTTP port (metrics=True; filled from ready acks)
        self.metrics_ports: dict = {}
        # rank -> "platform:id (kind)" each daemon computes on (ready acks)
        self.daemon_devices: dict = {}
        # per-task trace chunks from every rank (plus whatever the caller
        # extends with, e.g. the DealerDaemon's chunks)
        self.trace_chunks: list = []
        # driver-side wall clock of every submit->collect round trip
        # (uniform across prep / live / plain paths -- PartyResult.wall_s
        # is the program only)
        self.task_walls: list = []
        self._closed = False
        self._poisoned: str | None = None
        self.tasks_run = 0
        self._task_id = 0
        # async-dispatch state: submit_nowait enqueues atomically under
        # _sub_lock (the four task queues must agree on task order --
        # the daemons execute in queue order, and diverging orders would
        # deadlock the lock-step mesh); collect routes the shared result
        # queue into per-task buckets under _res_lock
        self._sub_lock = threading.Lock()
        self._res_lock = threading.Lock()
        self._results: dict = {}         # task_id -> [PartyResult...]
        self._errors: dict = {}          # rank -> traceback text
        # _free_ports probes-then-releases, so a concurrently booting
        # process can win the race to a probed port; retry the whole mesh
        # with fresh ports on EADDRINUSE (fail-fast on the first boot
        # error, so a lost race costs milliseconds, not a full timeout)
        for attempt in range(1, PORT_RETRIES + 1):
            self._task_qs = [ctx.Queue() for _ in range(4)]
            # per-rank control queues (live prep streaming): bounded, so a
            # dealer running ahead of consumption blocks instead of
            # buffering unbounded sessions in flight
            self.ctrl_queues = ([ctx.Queue(maxsize=2 * live_ahead)
                                 for _ in range(4)] if live_prep else None)
            self._out_q = ctx.Queue()
            endpoints = [("127.0.0.1", p) for p in _free_ports(4)]
            args = [(rank, endpoints, cfg, self._task_qs[rank],
                     self.ctrl_queues[rank] if live_prep else None,
                     self._out_q) for rank in range(4)]
            self._procs = [
                ctx.Process(target=_daemon_main, args=a, daemon=True)
                if devices is None else _PinnedDaemon(devices[a[0]], a)
                for a in args]
            for p in self._procs:
                p.start()
            try:
                acks = self._collect(lambda item: item[0] == "ready",
                                     self.timeout, fail_fast=True)
                self.metrics_ports = {a[1]: a[3] for a in acks}
                self.daemon_devices = {a[1]: a[4] for a in sorted(acks)}
                break
            except Exception as e:
                self._teardown_procs()
                if attempt < PORT_RETRIES and _addr_in_use(str(e)):
                    _log.warning(
                        "cluster boot lost the free-port race "
                        "(EADDRINUSE); retrying with fresh ports "
                        "(attempt %d/%d)", attempt, PORT_RETRIES)
                    self._errors.clear()
                    continue
                self._closed = True
                raise

    def _teardown_procs(self) -> None:
        """Boot-retry teardown: stop whatever daemons of a failed attempt
        came up.  Daemons still dialing the half-built mesh are not
        reading their task queues, so terminate after a short grace."""
        for q in self._task_qs:
            try:
                q.put_nowait(None)
            except (OSError, ValueError, _queue.Full):
                pass
        for p in self._procs:
            p.join(timeout=0.5)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=1.0)

    # -- task round-trips --------------------------------------------------
    def _collect(self, is_ack, timeout: float,
                 fail_fast: bool = False) -> list:
        """Gather one boot ack per daemon; raise with the collected
        tracebacks as soon as all four have answered (ack or error) or on
        timeout/death.  ``is_ack`` filters tuple-shaped acks.
        ``fail_fast=True`` raises on the FIRST error instead of waiting
        for the stragglers -- at boot the other ranks keep dialing the
        dead listener until connect_timeout, and the port-retry loop
        wants to tear down and retry in milliseconds, not minutes."""
        got, errors = [], {}
        answered: set[int] = set()
        deadline = time.monotonic() + timeout
        while len(got) + len(errors) < 4:
            budget = deadline - time.monotonic()
            if budget <= 0:
                raise RuntimeError(
                    f"party daemons timed out after {timeout}s "
                    f"(acks {len(got)}/4, errors {sorted(errors)})")
            try:
                item = self._out_q.get(timeout=min(budget, 1.0))
            except Exception:
                # only daemons that never answered count as silent deaths;
                # a daemon that posted its error and exited is accounted for
                dead = [i for i, p in enumerate(self._procs)
                        if not p.is_alive() and i not in answered]
                if dead and self._out_q.empty():
                    raise RuntimeError(
                        f"party daemon(s) {dead} died without a result"
                        + (f"; collected errors:\n" + "\n".join(
                            f"--- P{r} ---\n{tb}"
                            for r, tb in sorted(errors.items()))
                           if errors else "")) from None
                continue
            if isinstance(item, tuple) and item[0] == "error":
                errors[item[1]] = item[2]
                answered.add(item[1])
                if fail_fast:
                    break
            elif isinstance(item, tuple) and is_ack(item):
                got.append(item)
                answered.add(item[1])
        if errors:
            msgs = "\n".join(f"--- P{r} ---\n{tb}"
                             for r, tb in sorted(errors.items()))
            raise RuntimeError(f"party daemon failures:\n{msgs}")
        return got

    def _check_usable(self) -> None:
        assert not self._closed, "cluster is closed"
        if self._poisoned is not None:
            raise ClusterPoisoned(
                "cluster poisoned by an earlier task failure -- the "
                "lock-step mesh is undefined and the daemons have stopped "
                "serving; tear this cluster down and spawn a fresh one. "
                f"Original failure:\n{self._poisoned}")

    def submit_nowait(self, program, *, seed: int = 0,
                      prep: str | None = None,
                      prep_session: int | None = None,
                      runtime_kwargs: dict | None = None,
                      timeout: float | None = None) -> TaskHandle:
        """Enqueue ``program(rt, rank)`` on all four daemons and return a
        ``TaskHandle`` immediately (gather with ``collect``).  The four
        task-queue puts happen atomically under a lock: the daemons
        execute strictly in queue order, so all four queues must agree on
        the task order or the lock-step mesh deadlocks.  Tasks pipeline
        on the daemon side -- submitting task k+1 while task k runs
        overlaps driver-side share packing with party-side execution."""
        self._check_usable()
        with self._sub_lock:
            self._check_usable()
            self._task_id += 1
            task = {"program": program, "seed": seed, "prep": prep,
                    "prep_session": prep_session,
                    "runtime_kwargs": dict(runtime_kwargs or {}),
                    "timeout": timeout or self.timeout,
                    "id": self._task_id}
            with self._res_lock:
                self._results[self._task_id] = []
            t0 = time.perf_counter()
            for q in self._task_qs:
                q.put(task)
        return TaskHandle(task_id=task["id"], submitted_at=t0,
                          timeout=timeout or self.timeout)

    def _route(self, item) -> None:
        """Route one result-queue item (caller holds ``_res_lock``)."""
        if isinstance(item, tuple) and item[0] == "error":
            self._errors[item[1]] = item[2]
        elif isinstance(item, PartyResult):
            bucket = self._results.get(item.task_id)
            if bucket is not None:
                bucket.append(item)
            # else: stale result of an abandoned (timed-out) task

    def collect(self, handle: TaskHandle,
                timeout: float | None = None) -> list:
        """Gather the four ``PartyResult``s of a ``submit_nowait`` task.
        Safe to call from several threads for different handles: every
        collector drains the shared result queue and routes items into
        per-task buckets, so nobody steals another task's results.

        A task failure, daemon death, or timeout POISONS the cluster:
        this collect raises with the daemons' tracebacks and every later
        ``submit``/``collect`` raises ``ClusterPoisoned``."""
        assert not self._closed, "cluster is closed"
        tid = handle.task_id
        deadline = time.monotonic() + (timeout or handle.timeout)
        try:
            while True:
                with self._res_lock:
                    if self._poisoned is not None:
                        # another collector hit the failure first; its
                        # raise carries the tracebacks, ours the summary
                        raise ClusterPoisoned(
                            "cluster poisoned while this task was in "
                            f"flight:\n{self._poisoned}")
                    bucket = self._results.get(tid)
                    if bucket is not None and len(bucket) == 4:
                        del self._results[tid]
                        results = sorted(bucket, key=lambda r: r.rank)
                        self.task_walls.append(
                            time.perf_counter() - handle.submitted_at)
                        self.tasks_run += 1
                        self.trace_chunks.extend(
                            r.trace for r in results if r.trace)
                        return results
                    if bucket is None:
                        raise RuntimeError(
                            f"task {tid} was never submitted or was "
                            "already collected")
                    if self._errors:
                        # grace-drain so the raise carries every rank's
                        # traceback, not just the first one routed
                        grace = time.monotonic() + 1.0
                        while (len(self._errors) < 4
                               and time.monotonic() < grace):
                            try:
                                self._route(self._out_q.get(timeout=0.1))
                            except Exception:
                                if all(not p.is_alive()
                                       for p in self._procs):
                                    break
                        msgs = "\n".join(
                            f"--- P{r} ---\n{tb}" for r, tb
                            in sorted(self._errors.items()))
                        raise RuntimeError(
                            f"party daemon failures:\n{msgs}")
                rem = deadline - time.monotonic()
                if rem <= 0:
                    raise RuntimeError(
                        f"party daemons timed out after "
                        f"{timeout or handle.timeout}s on task {tid} "
                        f"({len(self._results.get(tid) or [])}/4 results)")
                try:
                    item = self._out_q.get(timeout=min(rem, 0.25))
                except Exception:
                    with self._res_lock:
                        done = {r.rank for r
                                in self._results.get(tid) or []}
                        dead = [i for i, p in enumerate(self._procs)
                                if not p.is_alive() and i not in done
                                and i not in self._errors]
                    if dead and self._out_q.empty():
                        raise RuntimeError(
                            f"party daemon(s) {dead} died without a "
                            f"result on task {tid}") from None
                    continue
                with self._res_lock:
                    self._route(item)
        except BaseException as e:
            with self._res_lock:
                if self._poisoned is None:
                    self._poisoned = f"{type(e).__name__}: {e}"
                self._results.pop(tid, None)
            raise

    def submit(self, program, *, seed: int = 0, prep: str | None = None,
               prep_session: int | None = None,
               runtime_kwargs: dict | None = None,
               timeout: float | None = None) -> list:
        """Run ``program(rt, rank)`` as one task across the four daemons;
        returns the per-rank ``PartyResult``s (measured deltas for this
        task).  ``prep="bank"`` consumes the next PrepBank session and
        executes online-only (offline sends forbidden on the wire);
        ``prep_session`` pins the session index (step-indexed training
        prep: session k is step k's material, so resumed runs seek past
        spent sessions and replays fail loudly).

        Blocking convenience over ``submit_nowait`` + ``collect``; the
        poisoning contract is theirs."""
        handle = self.submit_nowait(program, seed=seed, prep=prep,
                                    prep_session=prep_session,
                                    runtime_kwargs=runtime_kwargs,
                                    timeout=timeout)
        return self.collect(handle, timeout=timeout)

    @property
    def inflight(self) -> int:
        """Submitted-but-not-collected tasks (pool-scheduler load
        signal)."""
        with self._res_lock:
            return len(self._results)

    # -- observability -----------------------------------------------------
    def merged_trace(self, extra_chunks=()) -> dict:
        """One Chrome trace-event document over every chunk collected so
        far (all tasks, all four ranks) plus ``extra_chunks`` (e.g. the
        DealerDaemon's)."""
        from ...obs import merge_chunks
        return merge_chunks([*self.trace_chunks, *extra_chunks])

    def save_trace(self, path, extra_chunks=()) -> dict:
        """Merge and write the cluster timeline to ``path`` (Perfetto /
        chrome://tracing); returns the merged document."""
        from ...obs import write_chrome_trace
        return write_chrome_trace(path,
                                  [*self.trace_chunks, *extra_chunks])

    def alive(self) -> dict:
        """{rank: daemon process is alive} -- the liveness half of the
        health probes."""
        return {rank: p.is_alive() for rank, p in enumerate(self._procs)}

    def scrape(self, timeout: float = 2.0) -> dict:
        """Scrape every daemon's metrics exporter: {rank: snapshot|None}
        (None for a down daemon or a cluster built with metrics=False)."""
        from ...obs.health import _try_scrape
        return {rank: _try_scrape(port, timeout)
                for rank, port in sorted(self.metrics_ports.items())}

    def health(self, dealer=None, **kw) -> dict:
        """One cluster health document (docs/OBSERVABILITY.md): scrape
        all four exporters (plus the dealer's when attached), evaluate
        the stall/lag/liveness probes, and report ``healthy``."""
        from ...obs.health import cluster_health
        return cluster_health(self, dealer=dealer, **kw)

    # -- lifecycle ---------------------------------------------------------
    @property
    def poisoned(self) -> str | None:
        """The first-failure summary if a task poisoned the cluster."""
        return self._poisoned

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for q in self._task_qs:
            try:
                q.put_nowait(None)
            except (OSError, ValueError, _queue.Full) as e:
                # a daemon that cannot take its stop sentinel will be
                # terminated below -- say so instead of masking it
                _log.warning("cluster close: could not signal a daemon to "
                             "stop (%s: %s); it will be terminated",
                             type(e).__name__, e)
        for q in self.ctrl_queues or ():
            try:
                q.put_nowait(None)
            except _queue.Full:
                pass        # backpressured control stream; daemons exit via
                            # their task queues and the threads die with them
            except (OSError, ValueError) as e:
                _log.warning("cluster close: control queue teardown failed "
                             "(%s: %s)", type(e).__name__, e)
        for p in self._procs:
            p.join(timeout=5.0)
        for rank, p in enumerate(self._procs):
            if p.is_alive():
                _log.warning("party daemon P%d did not exit within 5s "
                             "(hung task or blocked join); terminating it",
                             rank)
                p.terminate()
                p.join(timeout=2.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def run_four_parties(program, *, ring: Ring = RING64, seed: int = 0,
                     timeout: float = DEFAULT_TIMEOUT, tampers=(),
                     net_model=None, runtime_kwargs=None,
                     prep_path: str | None = None,
                     prep: str | None = None, trace: bool = False) -> list:
    """One-shot: spawn a cluster, run ``program(rt, rank)``, tear down.

    Returns the four ``PartyResult``s ordered by rank.  ``tampers`` is a
    sequence of keyword dicts forwarded to ``Transport.tamper`` in every
    process.  ``net_model`` (a ``NetModel``) wraps each party's transport
    in a ``NetModelTransport`` and fills ``PartyResult.modeled_s``.
    ``trace=True`` (or ``TRIDENT_TRACE=1``) fills ``PartyResult.trace``
    with each rank's trace chunk (merge with ``repro.obs.merge_chunks``).
    """
    with PartyCluster(ring=ring, timeout=timeout, tampers=tampers,
                      net_model=net_model, prep_path=prep_path,
                      trace=trace) as cluster:
        return cluster.submit(program, seed=seed, prep=prep,
                              runtime_kwargs=runtime_kwargs,
                              timeout=timeout)
