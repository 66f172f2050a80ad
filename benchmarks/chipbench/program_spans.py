"""From a profiler trace to the program's own threads: which host thread
the device waited on inside a harness span, and in which program span.

With the program's tracer on the profiler sink (``repro.obs.Tracer(label,
sink="profiler")``), each span of the program is a TraceMe named
``trident.<span>`` on the host line of the thread that ran it.  The
serving stream runs two program threads inside ``serve.flush``:

* the dealer (``prep-dealer``), whose outermost spans are ``trident.deal``
  (metadata ``cpu_s``: the thread's CPU time in the deal);
* the online thread (the gateway's collector), whose outermost spans are
  ``trident.prep.wait`` (the wait for the dealer's next store) and
  ``trident.serve.batch.online`` (metadata ``cpu_s``).

Threads are keyed by host line, not by line name: two Python threads get
two lines, both named after the process.

Each idle slice of the device inside the window is put down to one
thread: the one that was inside a JAX call (``PjitFunction(...)``,
``DevicePut``) at the slice's middle (both, half each, where both were);
where neither was, the online thread unless it was waiting for prep, and
otherwise the dealer.  The slice is named
``<harness span>/<role>:<innermost program span>/<dispatch|python>``, the
program span without its ``trident.`` prefix (``-`` where the thread was
in none).  Where the trace holds no program thread, a slice is named as
``trace_reduce.py`` names it.
"""
from __future__ import annotations

import bisect
import collections

from trace_reduce import (DISPATCH_PREFIXES, ENQUEUE, HARNESS_PREFIXES,
                          WINDOW, _clip, _innermost, _union)

PREFIX = "trident."
ROLES = {"deal": PREFIX + "deal", "online": PREFIX + "serve.batch.online"}
PREP_WAIT = PREFIX + "prep.wait"
TOP = 20


def _segments(spans):
    """Nested (start, end, name) spans of one thread -> sorted disjoint
    (start, end, innermost name) segments."""
    out, stack, t = [], [], 0

    def close_until(x):
        nonlocal t
        while stack and stack[-1][1] <= x:
            _, e, name = stack.pop()
            if e > t:
                out.append((t, e, name))
                t = e

    for s, e, name in sorted(spans, key=lambda v: (v[0], -v[1])):
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][2]))
        stack.append((s, e, name))
        t = s
    close_until(float("inf"))
    return out


class _Intervals:
    """Sorted disjoint intervals, each with a label; ``at(t)`` finds the
    label of the one holding ``t``."""

    def __init__(self, intervals):
        self.iv = sorted(intervals)
        self.starts = [iv[0] for iv in self.iv]

    def at(self, t):
        k = bisect.bisect_right(self.starts, t) - 1
        if k >= 0 and self.iv[k][1] > t:
            return self.iv[k][2] if len(self.iv[k]) > 2 else True
        return None

    def edges(self):
        return [x for iv in self.iv for x in iv[:2]]


def _overlap(a, b) -> float:
    """Total overlap of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _read(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, harness, enqueued, lines = [], [], {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            named = {line.name: line for line in plane.lines}
            ops, mods = named.get("XLA Ops"), named.get("XLA Modules")
            if ops is None:
                continue
            devices.append((
                [(e.start_ns, e.end_ns) for e in ops.events],
                [(e.start_ns, dict(e.stats).get("run_id"))
                 for e in (mods.events if mods is not None else ())]))
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                spans, calls = [], []
                for e in line.events:
                    name = e.name
                    if name.startswith(PREFIX):
                        spans.append((e.start_ns, e.end_ns, name,
                                      dict(e.stats)))
                    elif name.startswith(DISPATCH_PREFIXES):
                        calls.append((e.start_ns, e.end_ns))
                    elif name == ENQUEUE:
                        run_id = dict(e.stats).get("run_id")
                        if run_id is not None:
                            enqueued[run_id] = e.start_ns
                    elif name == WINDOW or name.startswith(HARNESS_PREFIXES):
                        harness.append((name, e.start_ns, e.end_ns))
                lines.append((f"{plane.name}#{k}", spans, calls))
    return devices, harness, enqueued, lines


def _role(spans):
    """The role of a line from its outermost program spans."""
    outer, end = set(), None
    for s, e, name, _ in sorted(spans, key=lambda v: (v[0], -v[1])):
        if end is None or s >= end:
            outer.add(name)
            end = e
        else:
            end = max(end, e)
    for role, name in ROLES.items():
        if name in outer:
            return role
    return None


def reduce(path: str) -> dict:
    """Reduce one trace: ``program_threads``, ``program_spans`` (window
    sums), ``idle_gaps`` [[name, idle seconds]] (the largest),
    ``idle_by_role`` {harness span/role/state: idle seconds} and
    ``named_share`` {harness span: share of its idle time put down to a
    program thread}."""
    devices, harness, enqueued, lines = _read(path)
    windows = [(s, e) for n, s, e in harness if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found "
                           f"{len(windows)}")
    lo, hi = windows[0]
    harness = [(n, s, e) for n, s, e in harness if n != WINDOW]

    roles = collections.defaultdict(list)     # role -> [(key, spans, calls)]
    every_call = []
    for key, spans, calls in lines:
        every_call.extend(calls)
        role = _role(spans)
        if role is not None:
            roles[role].append((key, spans, calls))

    threads, sums = [], collections.Counter()
    inner, in_call, waiting = {}, {}, []
    for role, members in sorted(roles.items()):
        segs, calls_all = [], []
        for key, spans, calls in members:
            calls = _union(_clip(calls, lo, hi))
            calls_all.extend(calls)
            segs.extend(_segments([(s, e, n) for s, e, n, _ in spans]))
            own = [(s, e, st) for s, e, n, st in spans
                   if n == ROLES[role] and lo <= s < hi]
            threads.append({
                "line": key, "role": role, "spans": len(own),
                "span_s": sum(min(e, hi) - s for s, e, _ in own) / 1e9,
                "jax_call_s": _overlap(
                    _union([(s, min(e, hi)) for s, e, _ in own]),
                    calls) / 1e9,
                "cpu_s": sum(float(st.get("cpu_s", 0.0))
                             for _, _, st in own)})
            sums[f"{role}_spans"] += len(own)
            sums[f"{role}_s"] += threads[-1]["span_s"]
            sums[f"{role}_cpu_s"] += threads[-1]["cpu_s"]
            if role == "online":
                wait = [(s, min(e, hi)) for s, e, n, _ in spans
                        if n == PREP_WAIT and lo <= s < hi]
                sums["prep_wait_spans"] += len(wait)
                sums["prep_wait_s"] += sum(e - s for s, e in wait) / 1e9
                waiting.extend(wait)
        inner[role] = _Intervals(segs)
        in_call[role] = _Intervals(_union(calls_all))
    waiting = _Intervals(_union(waiting))
    any_call = _Intervals(_union(_clip(every_call, lo, hi)))

    cuts = sorted({x for _, s, e in harness for x in (s, e)}
                  | {x for r in roles for x in inner[r].edges()}
                  | {x for r in roles for x in in_call[r].edges()}
                  | set(waiting.edges()))
    idle = collections.Counter()
    for ops, mods in devices:
        shift = max((enqueued[r] - s for s, r in mods if r in enqueued),
                    default=0.0)
        busy = _union(_clip([(s + shift, e + shift) for s, e in ops],
                            lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            i, j = bisect.bisect_right(cuts, gs), bisect.bisect_left(cuts, ge)
            pts = [gs, *cuts[i:j], ge]
            for ps, pe in zip(pts, pts[1:]):
                mid = (ps + pe) / 2
                for name, share in _attribute(mid, harness, roles, inner,
                                              in_call, waiting, any_call):
                    idle[name] += share * (pe - ps) / 1e9
    n = max(len(devices), 1)
    idle = {k: v / n for k, v in idle.items()}
    named, total = collections.Counter(), collections.Counter()
    by_role = collections.Counter()
    for k, v in idle.items():
        span, rest = k.split("/", 1)
        total[span] += v
        if ":" in rest:
            named[span] += v
            role, state = rest.split(":", 1)[0], rest.rsplit("/", 1)[1]
            by_role[f"{span}/{role}/{state}"] += v
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "program_threads": threads,
        "program_spans": dict(sorted(sums.items())),
        "idle_gaps": [[k, v] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_by_role": dict(sorted(by_role.items())),
        "idle_s": sum(idle.values()),
        "named_share": {k: named[k] / v for k, v in sorted(total.items())
                        if v},
    }


def _attribute(t, harness, roles, inner, in_call, waiting, any_call):
    """[(idle slice name, share)] at time ``t``."""
    span = _innermost(harness, t)
    if not roles:
        return [(f"{span}/{'dispatch' if any_call.at(t) else 'python'}",
                 1.0)]
    calling = [r for r in sorted(roles) if in_call[r].at(t)]
    if calling:
        picked, state = calling, "dispatch"
    elif "online" in roles and ("deal" not in roles or not waiting.at(t)):
        picked, state = ["online"], "python"
    else:
        picked, state = ["deal"], "python"
    out = []
    for role in picked:
        where = inner[role].at(t)
        where = where[len(PREFIX):] if where else "-"
        out.append((f"{span}/{role}:{where}/{state}", 1.0 / len(picked)))
    return out


def per_layer(reduced: dict) -> dict:
    """The serving stream's per-layer readings from ``reduce``: None where
    the trace holds nothing to read."""
    sums = reduced.get("program_spans", {})
    batches = sums.get("online_spans", 0)
    online = [t for t in reduced.get("program_threads", [])
              if t["role"] == "online"]

    def share(part, whole):
        return 100.0 * sums[part] / sums[whole] if sums.get(whole) else None

    return {
        "prep_wait_ms_per_batch.serve":
            1e3 * sums.get("prep_wait_s", 0.0) / batches
            if batches else None,
        "dealer_cpu_share.serve": share("deal_cpu_s", "deal_s"),
        "online_cpu_share.serve": share("online_cpu_s", "online_s"),
        "online_dispatch_ms_per_batch.serve":
            1e3 * sum(t["jax_call_s"] for t in online) / batches
            if batches else None,
    }
