"""Structured tracing for the Trident runtime (the observability plane).

A ``Tracer`` is a per-process buffer of timestamped events -- spans
(duration work: a protocol, a kernel launch, a wire round), instants
(point events: a message send, a streamed prep session), and counters
(gauges: queue depths).  Tracing is OFF by default: every instrumented
seam holds a reference to the process tracer and guards its recording
with a single ``tracer.enabled`` attribute check, so a disabled run pays
one branch per hook and nothing else -- wire accounting, CostTally
equality, and bit-identity are untouched by construction (the tracer
never feeds values back into the protocols).

Enablement:

  * ``TRIDENT_TRACE=1`` in the environment -- the process tracer comes up
    enabled at first use; spawned party/dealer daemons inherit the
    environment, so one variable traces the whole 4-process cluster;
  * ``install_tracer(Tracer(...))`` -- explicit, per-process (what
    ``PartyCluster(trace=True)`` does inside each daemon).

A tracer has one of two sinks:

  * ``"buffer"`` (the default): each process buffers its own events
    against its own ``perf_counter`` clock and remembers the perf->epoch
    offset taken at tracer creation; ``drain()`` snapshots the buffer
    into a self-describing **chunk** (label, rank, epoch, events) that
    can cross a process boundary as a plain pickle/JSON value.
    ``repro.obs.merge`` aligns chunks from the four party daemons plus
    the dealer into one Chrome trace-event timeline
    (docs/OBSERVABILITY.md);
  * ``"profiler"``: every context span becomes a
    ``jax.profiler.TraceAnnotation`` (a TraceMe) named ``trident.<span>``,
    so it lands in the JAX profiler's ``.xplane.pb`` on the profiler's
    host clock, on the line of the thread that ran it; span arguments
    become TraceMe metadata.  Nothing is buffered: instants, counters and
    already-timed ``raw_span``s have no TraceMe form and are dropped.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
import time

TRACE_ENV = "TRIDENT_TRACE"
SINKS = ("buffer", "profiler")

# the profiler sink's span names carry this prefix, so a reader of the
# .xplane.pb tells the program's spans from a harness's own annotations
PROFILER_PREFIX = "trident."

# recv spans are only recorded when the receive actually blocked this
# long -- every recv as a span would drown the timeline in no-wait noise
RECV_SPAN_MIN_S = 1e-3


def tracing_enabled() -> bool:
    """Is tracing requested via the environment (``TRIDENT_TRACE=1``)?"""
    return os.environ.get(TRACE_ENV, "") == "1"


class NullSpan:
    """The span of a disabled tracer: entering, setting arguments and
    discarding are no-ops."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass

    def discard(self) -> None:
        pass


NULL_SPAN = NullSpan()


class NullTracer:
    """The disabled tracer: every hook is a no-op.  Instrumented code
    guards with ``if tracer.enabled:`` so the off path costs one branch."""

    enabled = False
    label = "null"
    rank = None

    def span(self, name, cat="", **args):
        return NULL_SPAN

    def raw_span(self, name, cat, t0, dur, **args) -> None:
        pass

    def instant(self, name, cat="", **args) -> None:
        pass

    def counter(self, name, value, cat="") -> None:
        pass

    def drain(self):
        return None


NULL_TRACER = NullTracer()


class _BufferSpan:
    """A context span of the buffer sink: timed from enter to exit, its
    arguments completed at exit with ``set``; ``discard`` drops it."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0")

    def __init__(self, tracer, name, cat, args):
        self._tracer, self._name, self._cat = tracer, name, cat
        self._args = args

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._tracer is not None:
            self._tracer.raw_span(self._name, self._cat, self._t0,
                                  time.perf_counter() - self._t0,
                                  **self._args)
        return False

    def set(self, **args) -> None:
        self._args.update(args)

    def discard(self) -> None:
        self._tracer = None


def _metadata(args: dict) -> dict:
    # TraceMe metadata holds numbers and strings; None means "not known"
    return {k: v for k, v in args.items() if v is not None}


class _ProfilerSpan:
    """A context span of the profiler sink: a TraceMe open while the work
    runs.  A TraceMe cannot be withdrawn, so ``discard`` is a no-op."""

    __slots__ = ("_trace_me",)

    def __init__(self, name, args):
        from jax.profiler import TraceAnnotation
        self._trace_me = TraceAnnotation(PROFILER_PREFIX + name,
                                         **_metadata(args))

    def __enter__(self):
        self._trace_me.__enter__()
        return self

    def __exit__(self, *exc):
        self._trace_me.__exit__(*exc)
        return False

    def set(self, **args) -> None:
        self._trace_me.set_metadata(**_metadata(args))

    def discard(self) -> None:
        pass


class Tracer:
    """An enabled per-process tracer (see the module docstring's sinks).

    Buffered events are dicts ``{ph, name, cat, ts, dur?, tid, args?}``
    with ``ts``/``dur`` in ``perf_counter`` seconds; ``ph`` follows the
    Chrome trace-event phases ("X" span, "i" instant, "C" counter).
    Appends are lock-protected: a party daemon's control thread (live
    prep) and task thread trace into the same buffer.
    """

    enabled = True

    def __init__(self, label: str | None = None, rank: int | None = None,
                 sink: str = "buffer"):
        if sink not in SINKS:
            raise ValueError(f"unknown tracer sink {sink!r}; one of {SINKS}")
        self.label = label or f"proc-{os.getpid()}"
        self.rank = rank
        self.sink = sink
        self._epoch = time.time() - time.perf_counter()
        self._lock = threading.Lock()
        self._events: list[dict] = []

    # -- recording ---------------------------------------------------------
    def _append(self, ev: dict) -> None:
        if self.sink != "buffer":
            return
        ev["tid"] = threading.get_ident()
        with self._lock:
            self._events.append(ev)

    def span(self, name: str, cat: str = "", **args):
        """A context span over the work inside the ``with``; the object
        entered has ``set(**args)`` for arguments known only at exit."""
        if self.sink == "buffer":
            return _BufferSpan(self, name, cat, args)
        if cat == "protocol":
            # buffered, a protocol span is the bare protocol ("mult") and
            # its cat says what it is; a TraceMe has only its name
            name = f"protocol.{name}"
        return _ProfilerSpan(name, args)

    def raw_span(self, name: str, cat: str, t0: float, dur: float,
                 **args) -> None:
        """Record an already-timed span (callers that only know after the
        fact whether the interval is worth a span, e.g. a blocking recv).
        The profiler sink drops it."""
        ev = {"ph": "X", "name": name, "cat": cat, "ts": t0, "dur": dur}
        if args:
            ev["args"] = args
        self._append(ev)

    def instant(self, name: str, cat: str = "", **args) -> None:
        ev = {"ph": "i", "name": name, "cat": cat,
              "ts": time.perf_counter()}
        if args:
            ev["args"] = args
        self._append(ev)

    def counter(self, name: str, value, cat: str = "") -> None:
        self._append({"ph": "C", "name": name, "cat": cat,
                      "ts": time.perf_counter(), "args": {"value": value}})

    # -- snapshotting ------------------------------------------------------
    def drain(self) -> dict:
        """Snapshot-and-reset: returns a self-describing trace chunk and
        clears the buffer (per-task deltas in the cluster daemons).  The
        chunk is plain data -- safe to pickle across the result queue or
        dump to JSON."""
        with self._lock:
            events, self._events = self._events, []
        return {"label": self.label, "rank": self.rank,
                "epoch": self._epoch, "events": events}


# ---------------------------------------------------------------------------
# The process tracer.
# ---------------------------------------------------------------------------
_process_tracer: NullTracer | Tracer | None = None


def get_tracer():
    """The process tracer: a ``Tracer`` if ``TRIDENT_TRACE=1`` (or one was
    installed), else the shared ``NULL_TRACER``."""
    global _process_tracer
    if _process_tracer is None:
        _process_tracer = Tracer() if tracing_enabled() else NULL_TRACER
    return _process_tracer


def install_tracer(tracer):
    """Set the process tracer explicitly; returns the previous one (tests
    restore it).  Pass ``NULL_TRACER`` to disable."""
    global _process_tracer
    prev = _process_tracer
    _process_tracer = tracer
    return prev


def ensure_tracer(label: str, rank: int | None = None):
    """Idempotently make sure the process traces: installs a fresh labeled
    ``Tracer`` unless an enabled one is already in place."""
    tr = get_tracer()
    if not tr.enabled:
        tr = Tracer(label, rank=rank)
        install_tracer(tr)
    return tr


# ---------------------------------------------------------------------------
# Instrumentation helpers.
# ---------------------------------------------------------------------------
def _prep_session(rt):
    store = getattr(rt.prep, "store", None)
    return getattr(store, "meta", {}).get("session") \
        if store is not None else None


def traced_protocol(name: str):
    """Decorate a runtime protocol entry point (``fn(rt, ...)``): the
    live metrics registry UNCONDITIONALLY counts the call and the number
    of CheckLedger verdicts the four parties recorded during it; when the
    runtime's tracer is enabled, the call additionally runs inside a span
    carrying prep attribution (mode + PrepStore session) and the same
    check count.  Untraced: two counter adds, then straight through."""
    from .registry import get_registry

    def count_checks(reg, rt, checks0) -> int:
        checks = sum(len(p.ledger.checks) for p in rt.parties) - checks0
        if checks:
            reg.counter("trident_protocol_checks_total",
                        "CheckLedger verdicts recorded").inc(checks)
        return checks

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(rt, *args, **kwargs):
            reg = get_registry()
            reg.counter("trident_protocol_calls_total",
                        "runtime protocol entries", protocol=name).inc()
            checks0 = sum(len(p.ledger.checks) for p in rt.parties)
            tr = rt.tracer
            if not tr.enabled:
                try:
                    return fn(rt, *args, **kwargs)
                finally:
                    count_checks(reg, rt, checks0)
            with tr.span(name, "protocol", prep=rt.prep.mode,
                         session=_prep_session(rt)) as sp:
                try:
                    return fn(rt, *args, **kwargs)
                finally:
                    sp.set(checks=count_checks(reg, rt, checks0))
        return wrapper
    return deco


@contextlib.contextmanager
def timed(stats, *attrs, span: str | None = None, cat: str = "serve",
          cpu: str | None = None, **span_args):
    """Accumulate the elapsed wall-clock into ``stats.<attr>`` for every
    attr named (the one consolidated spelling of the old inline
    ``t0 = perf_counter(); ...; stats.x += perf_counter() - t0``
    bookkeeping), and the calling thread's CPU time (``time.thread_time``)
    into ``stats.<cpu>`` when ``cpu`` is given.  When the process tracer
    is on, the work runs inside a span ``span`` that gets the thread CPU
    as ``cpu_s`` at exit.  Yields the span (``NULL_SPAN`` untraced), whose
    ``set`` adds arguments known only at exit."""
    tr = get_tracer()
    sp = tr.span(span, cat, **span_args) \
        if tr.enabled and span is not None else NULL_SPAN
    with sp:
        # the CPU interval is read inside the wall interval: cpu <= wall
        t0 = time.perf_counter()
        c0 = time.thread_time() if cpu is not None else 0.0
        try:
            yield sp
        finally:
            if cpu is not None:
                dc = time.thread_time() - c0
                setattr(stats, cpu, getattr(stats, cpu) + dc)
                sp.set(cpu_s=dc)
            dt = time.perf_counter() - t0
            for attr in attrs:
                setattr(stats, attr, getattr(stats, attr) + dt)


class Stopwatch:
    """Tiny context-manager wall clock; ``.s`` is the elapsed seconds."""

    s = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self._t0


def stopwatch() -> Stopwatch:
    return Stopwatch()
