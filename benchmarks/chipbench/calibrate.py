#!/usr/bin/env python3
"""The readings that the limits in ``limits/<cell>.json`` are set from.

Runs in one process, on the chip, at the cell's own size:

  sound    the program on a dozen seeds or more, each through the cell's
           driver: a stream of as many batches as a run of ``--seconds``
           answers;
  control  the program on a lower-precision ring (``--control-ring``,
           ell:frac, may repeat), on three seeds or more.

One JSON line per reading goes to ``--out``; a summary, the largest sound
and the smallest control reading of each number, to stdout.

    python3 benchmarks/chipbench/calibrate.py --workload nn_serve_b128 \
        --seeds 101-112 --control-seeds 201-203 --control-ring 32:8 \
        --seconds 51 --out chiprun_out/cal.jsonl
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as harness


def seed_range(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_range, required=True)
    ap.add_argument("--control-seeds", type=seed_range, default=[])
    ap.add_argument("--control-ring", action="append", default=[],
                    help="ell:frac of a control ring (repeatable)")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window per reading (serving: as a run's)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = harness.open_cell(args.workload, args.rehearse)
    import compare
    from repro.core.ring import Ring

    window = harness.Window(None)
    program_ring = cell.program_ring()
    runs = [("sound", f"{program_ring.ell}:{program_ring.frac}", s,
             program_ring) for s in args.seeds]
    for spec in args.control_ring:
        ell, frac = (int(x) for x in spec.split(":"))
        runs += [("control", spec, s, Ring(ell=ell, frac=frac))
                 for s in args.control_seeds]
    summary: dict = {}
    with open(args.out, "a") as fh:
        def record(kind, ring, seed, numbers, **extra):
            # a limit the run gave no number for reads as infinite, as in
            # run.py: a sound run that dropped answers or crashed then
            # leads the largest sound readings
            numbers = dict(numbers, **{
                k: c["value"] for k, c in
                compare.checks(numbers, cell.limits)[0].items()})
            line = {"workload": args.workload, "kind": kind, "ring": ring,
                    "seed": seed, "numbers": numbers, **extra}
            fh.write(json.dumps(line) + "\n")
            fh.flush()
            print(json.dumps(line), file=sys.stderr, flush=True)
            for k, v in numbers.items():
                summary.setdefault(f"{kind} {ring}", {}).setdefault(
                    k, []).append(v)

        for kind, ring_name, seed, ring in runs:
            t = time.perf_counter()
            try:
                out = cell.run(seed, args.seconds, window, ring=ring,
                               clock_start=time.perf_counter())
            except Exception as e:  # a control that crashes has failed
                record(kind, ring_name, seed, {}, error=repr(e))
                continue
            # a run that left queries unanswered has no number to compare
            record(kind, ring_name, seed, {} if out.failed else out.numbers,
                   failed=out.failed, attempted=out.attempted,
                   seconds=time.perf_counter() - t)
    for group, numbers in sorted(summary.items()):
        agg = max if group.startswith("sound") else min
        print(group, {k: agg(v) for k, v in numbers.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
