#!/usr/bin/env python3
"""Run one cell of the benchmark traced, with the program's tracer on the
profiler sink, and put the device's idle time down to the program's
threads (``program_spans.py``).

    python3 benchmarks/chipbench/attribute.py --workload <cell> \
        --seed <n> --seconds <s> [--program-spans 0|1]

Prints one JSON line: the window, the harness's own reduction of the
trace (``trace_reduce.py``), the program spans' reduction and its
per-layer readings.  ``--program-spans 0`` runs the same traced window
with the program's tracer off, so the two windows' lengths give what the
tracer costs when it is on.  ``--rehearse`` runs on the CPU at the
rehearsal's tiny size, where the trace holds no device.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile

import run as harness


def attribute(args) -> dict:
    cell = harness.open_cell(args.workload, args.rehearse)
    import program_spans
    import trace_reduce
    from repro import obs

    if args.program_spans:
        obs.install_tracer(obs.Tracer("chipbench", sink="profiler"))
    log_dir = tempfile.mkdtemp(prefix="chipbench-")
    try:
        out = cell.run(args.seed, args.seconds, harness.Window(log_dir))
        path = trace_reduce.find_xplane(log_dir)
        reduced = None if args.rehearse else trace_reduce.reduce(path)
        spans = program_spans.reduce(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return {
        "correct": cell.judge(out)[0],
        "program_spans_on": bool(args.program_spans),
        "window_s": out.window_s, "window_batches": out.layer.get("batches"),
        "end_to_end": out.end_to_end, "layer": out.layer,
        "per_layer": program_spans.per_layer(spans),
        "program": spans,
        "trace": None if reduced is None else {
            k: reduced[k] for k in ("window_s", "busy_s", "breakdown")},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny size")
    args = ap.parse_args(argv)
    try:
        result = attribute(args)
    except harness.BenchError as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
