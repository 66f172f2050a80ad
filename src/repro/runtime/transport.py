"""Measured message transport for the party-sliced runtime.

``Transport`` is the pluggable wire interface: point-to-point ``send`` /
``recv`` plus a ``round`` scope marking one synchronous communication step.
``MeasuredTransport`` holds the accounting every backend shares -- per-link
/ per-phase bits, round counting, tamper rules -- and delegates the actual
message movement to ``_put`` / ``_get``.  ``LocalTransport`` is the
in-memory backend (per-link deques); ``runtime.net.SocketTransport`` is the
multi-process TCP backend and inherits the *identical* accounting, so the
transport-vs-tally contract holds on a real wire too.

Accounting conventions (matching the paper's amortized lemmas):

  * a payload is ``count * nbits`` bits -- nbits is explicit because
    boolean shares carry sub-word payloads (a 1-bit share costs 1 bit);
  * hash / commitment copies are tallied at 0 bits (``nbits=0``); they
    still carry the sender's copy so receivers can recompute-and-compare,
    which is how tampering flips the abort flag;
  * a *round* is one synchronous step in which every party may send and
    then receive.  Nested ``round`` scopes of the same phase merge into the
    outermost one -- that is how composed protocols (e.g. Pi_MultTr's
    gamma exchange running alongside Pi_aSh) ship in a single round, the
    message-level realization of ``CostTally.parallel``.  A round scope
    that moves no bits counts zero rounds.
  * ``parallel`` / ``branch`` scopes mirror ``CostTally.parallel`` /
    ``CostTally.branch`` for *multi-round* protocols that run concurrently
    (e.g. sigmoid's two BitExt instances): rounds closed in sibling
    branches take the max, not the sum, exactly as the analytic tally
    counts them.  Bits always sum.

Fault injection: ``tamper`` registers a rule that corrupts matching
payloads in flight (adds ``delta`` mod 2^ell / XORs for boolean payloads).
The runtime's hash cross-checks then disagree and the receiving party's
ledger flips the abort flag -- asserted by tests/test_runtime.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from collections import defaultdict, deque

from repro.obs import NULL_SPAN, RECV_SPAN_MIN_S, get_registry, get_tracer

PHASES = ("offline", "online")


class PhaseViolation(RuntimeError):
    """A message was sent in a phase the transport forbids -- e.g. any
    offline-phase traffic during a PrepStore-backed online-only run."""


def _count(payload) -> int:
    shape = getattr(payload, "shape", ())
    return int(math.prod(shape)) if shape else 1


@dataclasses.dataclass
class TamperRule:
    """Corrupt payloads of messages matching (src, dst, tag substring)."""

    src: int | None = None
    dst: int | None = None
    tag: str | None = None
    delta: int = 1
    xor: bool = False
    count: int = 1          # how many matching messages to corrupt
    hit: int = 0

    def matches(self, src: int, dst: int, tag: str) -> bool:
        if self.hit >= self.count:
            return False
        if self.src is not None and src != self.src:
            return False
        if self.dst is not None and dst != self.dst:
            return False
        if self.tag is not None and self.tag not in tag:
            return False
        return True


class RoundFrames:
    """Per-phase additive accounting with parallel (max) / branch (sum)
    frames -- the transport-side twin of CostTally's round bookkeeping.

    ``total`` maps phase -> accumulated quantity (int rounds for the
    transports, float seconds for the network model).  ``add`` routes the
    amount to the nearest enclosing frame capturing that phase; parallel
    frames keep the max of their branches, branch frames sequence (sum).
    """

    def __init__(self):
        self.total = {p: 0 for p in PHASES}
        self._stack: list[dict] = []

    def add(self, phase: str, amount) -> None:
        frame = self._capturing_frame(phase)
        if frame is None:
            self.total[phase] += amount
        elif frame["mode"] == "seq":
            frame[phase] += amount
        else:
            frame[phase] = max(frame[phase], amount)

    def _capturing_frame(self, phase):
        for frame in reversed(self._stack):
            if phase in frame["phases"]:
                return frame
        return None

    @contextlib.contextmanager
    def parallel(self, phases=PHASES):
        frame = {"offline": 0, "online": 0, "phases": tuple(phases),
                 "mode": "par"}
        self._stack.append(frame)
        try:
            yield
        finally:
            self._stack.pop()
            self._fold_out(frame)

    @contextlib.contextmanager
    def branch(self):
        frame = {"offline": 0, "online": 0, "phases": PHASES, "mode": "seq"}
        self._stack.append(frame)
        try:
            yield
        finally:
            self._stack.pop()
            self._fold_out(frame)

    def _fold_out(self, frame):
        for phase in PHASES:
            if frame[phase]:
                parent = self._capturing_frame(phase)
                if parent is None:
                    self.total[phase] += frame[phase]
                elif parent["mode"] == "seq":
                    parent[phase] += frame[phase]
                else:
                    parent[phase] = max(parent[phase], frame[phase])


class Transport:
    """Wire interface the party-local protocols are written against."""

    def send(self, src: int, dst: int, payload, *, tag: str, nbits: int,
             phase: str) -> None:
        raise NotImplementedError

    def recv(self, dst: int, src: int, *, tag: str):
        raise NotImplementedError

    def round(self, phase: str):
        """Context manager scoping one synchronous communication round."""
        raise NotImplementedError

    def parallel(self, phases=PHASES):
        """Scope in which sibling branches' rounds overlap (max)."""
        raise NotImplementedError

    def branch(self):
        """One concurrently-running branch of an enclosing parallel()."""
        raise NotImplementedError


class MeasuredTransport(Transport):
    """Shared measurement layer: exact per-link, per-phase accounting.

    Subclasses implement ``_put`` (deliver a payload on the directed link)
    and ``_get`` (obtain the next payload of a (src, dst, tag) stream).
    """

    def __init__(self):
        self._frames = RoundFrames()
        # (src, dst) -> phase -> bits
        self.link_bits: dict[tuple, dict] = defaultdict(
            lambda: {p: 0 for p in PHASES})
        self.link_msgs: dict[tuple, int] = defaultdict(int)
        self.rounds = self._frames.total
        self.phase_bits = {p: 0 for p in PHASES}
        self._round_depth = {p: 0 for p in PHASES}
        self._round_traffic = {p: False for p in PHASES}
        self._tampers: list[TamperRule] = []
        self._forbidden: set[str] = set()
        # observability: the process tracer (NULL_TRACER unless enabled),
        # plus per-phase round indices / open-scope bits for round spans
        self.tracer = get_tracer()
        self._round_index = {p: 0 for p in PHASES}
        self._round_bits0 = {p: 0 for p in PHASES}
        # live metrics (always on): the registry double-books the wire --
        # trident_wire_bits_total must equal per_link() exactly, the
        # consistency contract tests/test_metrics.py asserts.  Hot-path
        # counters are cached per label set so a send pays dict.get + one
        # locked add, not a registry lookup.
        self.metrics = get_registry()
        self._m_bits: dict = {}
        self._m_msgs: dict = {}
        self._m_rounds: dict = {}
        self._m_recv_wait = self.metrics.counter(
            "trident_wire_recv_wait_us_total",
            "total wall-clock blocked in recv (us)")
        self._m_slow_recv = self.metrics.counter(
            "trident_wire_slow_recvs_total",
            f"receives that blocked >= {RECV_SPAN_MIN_S * 1e3:g} ms")

    # -- measurement -------------------------------------------------------
    def bits(self, phase: str | None = None) -> int:
        if phase is None:
            return sum(self.phase_bits.values())
        return self.phase_bits[phase]

    def per_link(self) -> dict:
        """{(src, dst): {"offline": bits, "online": bits}} for active links."""
        return {k: dict(v) for k, v in sorted(self.link_bits.items())}

    def totals(self) -> dict:
        """Same shape as CostTally.totals() -- directly comparable."""
        return {p: {"rounds": self.rounds[p], "bits": self.phase_bits[p]}
                for p in PHASES}

    # -- phase policing ----------------------------------------------------
    def forbid_phase(self, phase: str) -> None:
        """Make any subsequent ``send`` in `phase` raise ``PhaseViolation``.
        The online-only executor forbids "offline": a PrepStore-backed run
        must move zero offline bytes on the wire -- asserted, not assumed."""
        assert phase in PHASES, phase
        self._forbidden.add(phase)

    def allow_phase(self, phase: str) -> None:
        self._forbidden.discard(phase)

    # -- fault injection ---------------------------------------------------
    def tamper(self, *, src: int | None = None, dst: int | None = None,
               tag: str | None = None, delta: int = 1, xor: bool = False,
               count: int = 1) -> TamperRule:
        rule = TamperRule(src=src, dst=dst, tag=tag, delta=delta, xor=xor,
                          count=count)
        self._tampers.append(rule)
        return rule

    def _apply_tamper(self, src, dst, tag, payload):
        for rule in self._tampers:
            if rule.matches(src, dst, tag):
                rule.hit += 1
                payload = (payload ^ payload.dtype.type(rule.delta)
                           if rule.xor
                           else payload + payload.dtype.type(rule.delta))
        return payload

    # -- wire --------------------------------------------------------------
    @contextlib.contextmanager
    def round(self, phase: str):
        assert phase in PHASES, phase
        outermost = self._round_depth[phase] == 0
        sp = NULL_SPAN
        if outermost:
            self._round_traffic[phase] = False
            if self.tracer.enabled:
                # the span covers the whole outermost scope incl. the
                # backend flush -- the measured cost of one round
                sp = self.tracer.span(f"round[{phase}]", "wire.round",
                                      phase=phase)
                self._round_bits0[phase] = self.phase_bits[phase]
        self._round_depth[phase] += 1
        with sp:
            try:
                yield self
            finally:
                self._round_depth[phase] -= 1
                if outermost:
                    self._close_round(phase, sp)

    def _close_round(self, phase: str, sp) -> None:
        if self._round_traffic[phase]:
            self._frames.add(phase, 1)
            c = self._m_rounds.get(phase)
            if c is None:
                c = self._m_rounds[phase] = self.metrics.counter(
                    "trident_wire_round_scopes_total",
                    "traffic-bearing outermost round scopes "
                    "(parallel-overlapped scopes each count, so "
                    ">= the analytic round tally)", phase=phase)
            c.inc()
        self._round_flush(phase)
        if sp is NULL_SPAN:
            return
        if self._round_traffic[phase]:
            sp.set(index=self._round_index[phase],
                   bits=self.phase_bits[phase] - self._round_bits0[phase])
            self._round_index[phase] += 1
        else:
            sp.discard()                # a scope that moved nothing

    def parallel(self, phases=PHASES):
        return self._frames.parallel(phases)

    def branch(self):
        return self._frames.branch()

    def send(self, src: int, dst: int, payload, *, tag: str, nbits: int,
             phase: str) -> None:
        assert src != dst, f"self-send {src} ({tag})"
        if phase in self._forbidden:
            raise PhaseViolation(
                f"{phase} send P{src}->P{dst} ({tag}) on a transport that "
                f"forbids {phase}-phase traffic")
        assert self._round_depth[phase] > 0, \
            f"send outside a {phase} round scope ({tag})"
        bits = nbits * _count(payload)
        if bits:
            self._round_traffic[phase] = True
            self.phase_bits[phase] += bits
            self.link_bits[(src, dst)][phase] += bits
            c = self._m_bits.get((src, dst, phase))
            if c is None:
                c = self._m_bits[(src, dst, phase)] = self.metrics.counter(
                    "trident_wire_bits_total",
                    "measured wire bits (== per_link() exactly)",
                    src=src, dst=dst, phase=phase)
            c.inc(bits)
        self.link_msgs[(src, dst)] += 1
        c = self._m_msgs.get((src, dst))
        if c is None:
            c = self._m_msgs[(src, dst)] = self.metrics.counter(
                "trident_wire_msgs_total",
                "messages sent (zero-bit hash copies included)",
                src=src, dst=dst)
        c.inc()
        if self.tracer.enabled:
            self.tracer.instant("send", cat="wire.send", src=src, dst=dst,
                                tag=tag, bits=bits, phase=phase,
                                round=self._round_index[phase])
        payload = self._apply_tamper(src, dst, tag, payload)
        self._put(src, dst, tag, payload)

    def recv(self, dst: int, src: int, *, tag: str):
        t0 = time.perf_counter()
        payload = self._get(dst, src, tag)
        dt = time.perf_counter() - t0
        self._m_recv_wait.inc(dt * 1e6)
        if dt >= RECV_SPAN_MIN_S:
            self._m_slow_recv.inc()
            if self.tracer.enabled:
                # only blocking receives make the timeline -- a recv span
                # is the wait for the peer (or the network), not the copy
                self.tracer.raw_span("recv", "wire.recv", t0, dt, dst=dst,
                                     src=src, tag=tag)
        return payload

    # -- backend hooks -----------------------------------------------------
    def _put(self, src: int, dst: int, tag: str, payload) -> None:
        raise NotImplementedError

    def _get(self, dst: int, src: int, tag: str):
        raise NotImplementedError

    def _round_flush(self, phase: str) -> None:
        """Called when the outermost round scope of `phase` closes; backends
        that coalesce outgoing messages (SocketTransport) flush here."""


class LocalTransport(MeasuredTransport):
    """In-memory transport: all four parties lock-step in one process."""

    def __init__(self):
        super().__init__()
        self._queues: dict[tuple, deque] = defaultdict(deque)

    def _put(self, src: int, dst: int, tag: str, payload) -> None:
        self._queues[(src, dst, tag)].append(payload)

    def _get(self, dst: int, src: int, tag: str):
        q = self._queues[(src, dst, tag)]
        assert q, f"recv on empty link P{src}->P{dst} ({tag})"
        return q.popleft()
