"""From a profiler trace (``.xplane.pb``) to the device's busy and idle
time, the device time of each XLA module, and the breakdown.

The trace holds one plane per device (``/device:TPU:<n>``) and one for the
host (``/host:CPU``).  A device plane's ``XLA Ops`` line has one event per
operation the device ran; its ``XLA Modules`` line one event per program
(``jit_<name>(<id>)``).  The harness marks its own phases with
``jax.profiler.TraceAnnotation`` on the host: the window is the span named
``WINDOW``, and each idle gap of the device is named by the innermost
harness span (``train.step``, ``serve.flush``, ...) that covers its
middle, and by what the host threads were doing there: ``dispatch`` where
one of them was inside a JAX call (``PjitFunction(...)``, ``DevicePut``),
``python`` where none was.

The device's timestamps are about a millisecond off the host's (on a v5e
a device program appeared to start 1.2 ms before the host enqueued it).
Each device plane is shifted by the least amount that puts every program
after its host ``DoEnqueueProgram`` (matched by ``run_id``).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW = "bench.window"
HARNESS_PREFIXES = ("train.", "serve.")
DISPATCH_PREFIXES = ("PjitFunction(", "DevicePut")
ENQUEUE = "DoEnqueueProgram"
TOP = 10


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(event_name: str) -> str:
    """``jit__ring_matmul(123)`` -> ``jit__ring_matmul``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(path: str) -> dict:
    """Reduce one trace: ``window_s``, ``busy_s`` (averaged over the
    devices), ``modules`` {name: device seconds}, ``idle_by_span``
    {harness span: idle seconds} and ``breakdown``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host_spans, dispatch, enqueued = [], [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            ops = lines.get("XLA Ops")
            mods = lines.get("XLA Modules")
            if ops is None:
                continue
            devices.append((
                [(e.start_ns, e.end_ns) for e in ops.events],
                [(module_name(e.name), e.start_ns, e.end_ns,
                  dict(e.stats).get("run_id"))
                 for e in (mods.events if mods is not None else ())]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(DISPATCH_PREFIXES):
                        dispatch.append((e.start_ns, e.end_ns))
                    elif name == ENQUEUE:
                        run_id = dict(e.stats).get("run_id")
                        if run_id is not None:
                            enqueued[run_id] = e.start_ns
                    elif name == WINDOW or name.startswith(HARNESS_PREFIXES):
                        host_spans.append((name, e.start_ns, e.end_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found "
                           f"{len(windows)}")
    if not devices:
        raise RuntimeError("the trace holds no device plane with XLA Ops")
    lo, hi = windows[0]
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW]
    dispatch = _union(_clip(dispatch, lo, hi))
    dispatch_starts = [s for s, _ in dispatch]
    busy_total, modules = 0.0, collections.Counter()
    idle = collections.Counter()
    shifts = []
    for ops, mods in devices:
        shift = max((enqueued[r] - s for _, s, _, r in mods
                     if r in enqueued), default=0.0)
        shifts.append(shift)
        ops = [(s + shift, e + shift) for s, e in ops]
        busy = _union(_clip(ops, lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for name, s, e, _ in mods:
            s, e = s + shift, e + shift
            if e > lo and s < hi:
                modules[name] += (min(e, hi) - max(s, lo)) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            # cut the gap where a harness span begins or ends inside it
            cuts = sorted({gs, ge, *(x for _, s, e in spans for x in (s, e)
                                     if gs < x < ge)})
            for ps, pe in zip(cuts, cuts[1:]):
                mid = (ps + pe) / 2
                k = bisect.bisect_right(dispatch_starts, mid) - 1
                host = ("dispatch" if k >= 0 and dispatch[k][1] > mid
                        else "python")
                idle[f"{_innermost(spans, mid)}/{host}"] += (pe - ps) / 1e9
    n = len(devices)
    modules = {k: v / n for k, v in modules.items()}
    idle = {k: v / n for k, v in idle.items()}
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "devices": n,
        "clock_shift_s": [x / 1e9 for x in shifts],
        "modules": modules,
        "idle_by_span": idle,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                modules.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[k, v] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }


def _innermost(spans, t) -> str:
    best, best_len = "outside harness spans", None
    for name, s, e in spans:
        if s <= t < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best
