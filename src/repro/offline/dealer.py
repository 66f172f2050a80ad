"""The prep-ahead dealer: run a protocol program's offline half, ahead of
time, and materialize the per-party preprocessing into a PrepStore.

``deal(program)`` executes ``program(rt)`` on a runtime in **deal mode**
(``DealPrep``): every protocol runs its offline half for real -- PRF
sampling in the exact counter order the interleaved path uses, offline
messages moving (and being measured) on the dealer's transport -- records
the per-party material under its tag, and skips its online half, so only
lambda-level data flows between protocols.  The program therefore needs
input *shapes*, not input values; pass zeros (``Workload`` does).

The dealer asserts its own dual of the online-only contract: a deal pass
moves **zero online bytes** (the workload must be data-independent).
Offline-phase malicious checks (trunc-pair relation, Bit2A/B2A/BitInj
verifications, aSh hash exchanges) run at deal time; ``DealReport.abort``
carries their verdict -- a corrupted dealer is caught before any store is
served.

``deal_sessions`` deals the same (or per-session) programs repeatedly into
a ``PrepBank`` -- one session per serving batch, each from its own seed --
which party daemons load once at startup.
"""
from __future__ import annotations

import dataclasses
import logging
import time

from ..core.ring import RING64, Ring
from ..obs import NULL_SPAN, get_tracer
from .store import DealPrep, PrepBank, PrepError, PrepStore

_log = logging.getLogger(__name__)


@dataclasses.dataclass
class DealReport:
    """What one dealer pass produced and moved (per-pass deltas)."""

    entries: int
    offline_rounds: int
    offline_bits: int
    wall_s: float
    abort: bool
    summary: dict
    cpu_s: float = 0.0                  # the dealing thread's CPU time


def deal(program, *, ring: Ring = RING64, seed: int = 0, transport=None,
         store: PrepStore | None = None, meta: dict | None = None,
         runtime_kwargs: dict | None = None):
    """Run ``program(rt)`` in deal mode; returns (PrepStore, DealReport).

    ``transport`` defaults to a fresh ``LocalTransport``; pass a
    ``NetModelTransport``-wrapped one to also price the offline phase
    under a LAN/WAN model.  ``seed`` must match the seed the interleaved
    twin would use -- it IS the preprocessing (the F_setup streams).
    """
    from ..runtime import FourPartyRuntime, LocalTransport

    if store is None:
        store = PrepStore(meta={"ring_ell": ring.ell, "seed": seed,
                                **(meta or {})})
    tp = transport if transport is not None else LocalTransport()
    rt = FourPartyRuntime(ring, seed=seed, transport=tp,
                          prep=DealPrep(store), **(runtime_kwargs or {}))
    entries_before = len(store)
    before = tp.totals()                 # transports may be reused/stacked
    tracer = get_tracer()
    sp = tracer.span("deal", "prep", seed=seed,
                     session=store.meta.get("session")) \
        if tracer.enabled else NULL_SPAN
    with sp:
        t0 = time.perf_counter()
        c0 = time.thread_time()         # read inside the wall interval
        program(rt)
        cpu = time.thread_time() - c0
        wall = time.perf_counter() - t0
        totals = tp.totals()
        offline_bits = totals["offline"]["bits"] - before["offline"]["bits"]
        sp.set(entries=len(store) - entries_before,
               offline_bits=offline_bits, cpu_s=cpu)
    online = {k: totals["online"][k] - before["online"][k]
              for k in totals["online"]}
    if online["bits"] or online["rounds"]:
        raise PrepError(
            f"dealer pass moved online traffic ({online}): the "
            "program is not data-independent, cannot prep ahead")
    if bool(rt.abort_flag()):
        raise PrepError("dealer pass aborted: offline-phase consistency "
                        "checks failed")
    _log.debug("deal pass: %d entries, %d offline rounds, %d offline bits, "
               "%.3fs (seed %d, session %s)",
               len(store) - entries_before,
               totals["offline"]["rounds"] - before["offline"]["rounds"],
               offline_bits, wall, seed, store.meta.get("session"))
    return store, DealReport(
        entries=len(store) - entries_before,
        offline_rounds=totals["offline"]["rounds"]
        - before["offline"]["rounds"],
        offline_bits=offline_bits,
        wall_s=wall,
        abort=False,
        summary=store.summary(),
        cpu_s=cpu,
    )


def deal_sessions(programs, *, ring: Ring = RING64, base_seed: int = 0,
                  runtime_kwargs: dict | None = None,
                  meta: dict | None = None) -> tuple:
    """Deal one PrepStore per program in ``programs`` (seeds base_seed+k)
    into a PrepBank; returns (bank, [DealReport])."""
    bank = PrepBank()
    reports = []
    for k, program in enumerate(programs):
        store, rep = deal(program, ring=ring, seed=base_seed + k,
                          runtime_kwargs=runtime_kwargs,
                          meta={"session": k, **(meta or {})})
        bank.add(store)
        reports.append(rep)
    return bank, reports
