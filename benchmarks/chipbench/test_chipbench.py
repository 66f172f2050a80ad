"""Checks of the benchmark itself, on the CPU:

* the trace reduction pins its numbers on a small trace recorded on a
  v5e chip (``testdata/``);
* each cell, rehearsed at a tiny size, passes its comparison with the
  plain reference;
* the control (the program on the lower-precision ring of
  ``limits/<cell>.json``) fails it;
* so does every fault its driver can have (``drivers/<driver>.py``
  ``faults``), and every fault of any cell (``CELL_FAULTS``), each
  planted in the program underneath the timed path;
* ``nn_serve_b128`` keeps the weights, queries and ``score_gap`` pinned
  in ``testdata/pinned_nn_serve_b128.json``.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chipbench
"""
from __future__ import annotations

import argparse
import hashlib
import json
import pathlib

import numpy as np
import pytest

import run as harness

HERE = pathlib.Path(__file__).resolve().parent
CELLS = tuple(w["name"] for w in harness.read_benchmark()["workloads"])


def rehearse(workload: str, control: bool = False, seed: int = 2**31 + 7,
             bench=harness):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.0,
                              trace=0, rehearse=True, control=control)
    return bench.run(args)


def digest(arrays: dict) -> str:
    """sha256 over each array in key order: the key, then the array as
    C-ordered little-endian float64."""
    h = hashlib.sha256()
    for k in sorted(arrays):
        h.update(k.encode())
        h.update(np.ascontiguousarray(arrays[k], "<f8").tobytes())
    return h.hexdigest()


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
    assert "setup_s" in result["metrics"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    result = rehearse(workload, control=True)
    assert not result["correct"], result["checks"]


def ring_matmul_low32():
    from repro.core import ring as R
    original = R._ring_matmul

    def broken(a, b, ring):
        # a contraction that keeps half of the 64-bit ring's digits
        return original(a, b, ring) & ring.dtype(0xFFFFFFFF)

    return R, "_ring_matmul", broken


# the faults that any cell can have, whatever its driver: ``name ->
# plant()``, as a driver's ``faults``
CELL_FAULTS = {"ring_matmul_low32": ring_matmul_low32}


def _faults():
    out = []
    for w in CELLS:
        _, _, traffic, _ = harness.load_cell(w)
        driver = harness.plugin("drivers", traffic["driver"])
        faults = dict(driver.faults(), **CELL_FAULTS)
        out += [(w, name, plant) for name, plant in faults.items()]
    return out


FAULTS = _faults()


@pytest.mark.parametrize("workload,fault,plant", FAULTS,
                         ids=[f"{w}-{f}" for w, f, _ in FAULTS])
def test_fault_is_not_correct(monkeypatch, workload, fault, plant):
    harness.prepare_environment()
    monkeypatch.setattr(*plant())
    result = rehearse(workload)
    assert not result["correct"], result["checks"]


def test_calibrate_reads_a_run_that_dropped_answers_as_infinite(
        monkeypatch, tmp_path):
    """A sound run that left a query unanswered gives no number; the
    readings that limits are set from then show it as infinite."""
    import calibrate
    harness.prepare_environment()
    from repro.serve import party_server as PS
    original = PS.PartyPredictionServer.flush
    monkeypatch.setattr(PS.PartyPredictionServer, "flush",
                        lambda self: original(self)[:-1])
    out = tmp_path / "cal.jsonl"
    assert calibrate.main(["--workload", CELLS[0], "--seeds", "5",
                           "--out", str(out), "--rehearse"]) == 0
    line = json.loads(out.read_text())
    assert line["failed"] == 1
    assert line["numbers"] and all(v == float("inf")
                                   for v in line["numbers"].values())


def test_pinned_inputs_and_score_gap():
    """The cell's weights, its first two query batches and its rehearsal's
    ``score_gap`` read as the harness read them before the models and the
    drivers went into files of their own."""
    pin = json.loads(
        (HERE / "testdata" / "pinned_nn_serve_b128.json").read_text())
    result = rehearse(pin["workload"], seed=pin["seed"])
    assert result["checks"]["score_gap"]["value"] == pin["score_gap"]

    from common import sub_seed
    _, config, traffic, _ = harness.load_cell(pin["workload"])
    _, model = harness.resolve(config, traffic)
    sizes = {"cell": (config, traffic),
             "rehearsal": model.rehearsal(config, traffic)}
    for name, (cfg, trf) in sizes.items():
        b = trf["batch"]
        assert b == pin[name]["batch"]
        feed = model.feed(sub_seed(pin["seed"], "data"), cfg)
        weights = model.weights(sub_seed(pin["seed"], "weights"), cfg)
        queries = {"batch0": feed.queries(0, b), "batch1": feed.queries(b, b)}
        assert digest(weights) == pin[name]["weights_sha256"], name
        assert digest(queries) == pin[name]["queries_sha256"], name
