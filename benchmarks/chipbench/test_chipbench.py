"""Checks of the benchmark itself, on the CPU:

* the trace reduction pins its numbers on a small trace recorded on a
  v5e chip (``testdata/``);
* each cell, rehearsed at a tiny size, passes its comparison with the
  plain reference;
* the control (the program on the lower-precision ring of
  ``limits/<cell>.json``) fails it;
* so does every fault the cell can have, planted in the program
  underneath the timed path, and a ring matmul that keeps only the low
  32 bits of the configuration's 64-bit ring.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chipbench
"""
from __future__ import annotations

import argparse
import json
import pathlib

import numpy as np
import pytest

import run as harness

HERE = pathlib.Path(__file__).resolve().parent
CELLS = tuple(w["name"] for w in harness.read_benchmark()["workloads"])


def rehearse(workload: str, control: bool = False, seed: int = 2**31 + 7):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.0,
                              trace=0, rehearse=True, control=control)
    return harness.run(args)


def test_trace_reduction_pinned():
    import trace_reduce
    want = json.loads((HERE / "testdata" / "reduced.json").read_text())
    got = trace_reduce.reduce(trace_reduce.find_xplane(
        str(HERE / "testdata")))
    assert got == want


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct(workload):
    result = rehearse(workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    result = rehearse(workload, control=True)
    assert not result["correct"], result["checks"]


def _serve_fault(fault):
    from repro.serve import party_server as PS
    original = PS.PartyPredictionServer._run_batch_pipelined

    def broken(self, X, n):
        preds = np.array(original(self, X, n))
        if fault == "half_batch":
            preds[n // 2:] = 0.0        # half of the answers left out
        else:
            preds[0, 0] += 0.1          # one answer altered where produced
        return preds

    return PS.PartyPredictionServer, "_run_batch_pipelined", broken


def _ring_fault(fault):
    from repro.core import ring as R
    original = R._ring_matmul

    def broken(a, b, ring):
        # a contraction that keeps half of the 64-bit ring's digits
        return original(a, b, ring) & ring.dtype(0xFFFFFFFF)

    return R, "_ring_matmul", broken


FAULTS = [(w, f, _serve_fault) for w in CELLS
          for f in ("half_batch", "answer_altered")] + [
          (w, "ring_matmul_low32", _ring_fault) for w in CELLS]


@pytest.mark.parametrize("workload,fault,plant", FAULTS,
                         ids=[f"{w}-{f}" for w, f, _ in FAULTS])
def test_fault_is_not_correct(monkeypatch, workload, fault, plant):
    harness.prepare_environment()
    monkeypatch.setattr(*plant(fault))
    result = rehearse(workload)
    assert not result["correct"], result["checks"]
