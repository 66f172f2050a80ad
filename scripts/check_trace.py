#!/usr/bin/env python
"""Smoke-check a merged Chrome trace-event JSON from a traced cluster run.

CI runs this against netbench's ``--trace-out`` artifact: the trace must
parse, carry spans from ALL FOUR party ranks (``--expect-dealer`` also
requires the dealer's process), and contain the core span taxonomy
(wire rounds + sends; protocol spans ride on the same buffer).  A thin
gate, but it fails loudly if a rank's chunks ever stop making it back
over the result channel.

    python scripts/check_trace.py netbench_trace.json [--expect-dealer]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.obs import metrics_snapshot  # noqa: E402


def check(path: str, expect_dealer: bool = False) -> dict:
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"]
    assert events, f"{path}: empty trace"
    meta = doc.get("metadata", {})
    ranks = set(meta.get("ranks", ()))
    assert ranks == {0, 1, 2, 3}, \
        f"{path}: expected chunks from all four party ranks, got {ranks}"
    processes = meta.get("processes", {})
    if expect_dealer:
        assert "dealer" in processes, \
            f"{path}: no dealer process on the timeline ({processes})"
    # spans must actually cover every rank's process, not just be claimed
    # by the chunk metadata
    party_pids = {pid for label, pid in processes.items()
                  if label.startswith("party-P")}
    span_pids = {e["pid"] for e in events if e["ph"] == "X"}
    missing = party_pids - span_pids
    assert not missing, f"{path}: ranks with no spans: pids {missing}"
    snap = metrics_snapshot(doc)
    assert snap["rounds"].get("online", {}).get("count", 0) > 0, \
        f"{path}: no online wire rounds on the timeline"
    assert snap["sends"].get("online", {}).get("bits", 0) > 0, \
        f"{path}: no online bytes traced"
    return {"events": len(events), "processes": sorted(processes),
            "rounds": snap["rounds"], "cats": sorted(snap["spans"])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="merged Chrome trace-event JSON")
    ap.add_argument("--expect-dealer", action="store_true",
                    help="require the dealer daemon's process too")
    args = ap.parse_args()
    info = check(args.trace, expect_dealer=args.expect_dealer)
    print(f"[check_trace] OK: {args.trace} -- {info['events']} events, "
          f"processes {info['processes']}, span cats {info['cats']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
