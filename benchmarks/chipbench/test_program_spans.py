"""Checks of ``program_spans.py``, on the CPU:

* the reduction pins its numbers on a small two-thread trace recorded on
  a v5e chip (``testdata_threads/``, gzipped, written by
  ``record_threads_testdata.py``);
* the per-layer readings come out of a reduction as defined, and none
  where the trace holds no program span;
* nested spans flatten to their innermost names, and each idle slice
  goes to the thread the rule names;
* ``attribute.py``, rehearsed at a tiny size, finds both threads and
  every reading.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chipbench
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib

import pytest

import program_spans

HERE = pathlib.Path(__file__).resolve().parent
TESTDATA = HERE / "testdata_threads"


def test_program_spans_pinned(tmp_path):
    (trace,) = TESTDATA.glob("*.xplane.pb.gz")
    path = tmp_path / trace.stem
    path.write_bytes(gzip.decompress(trace.read_bytes()))
    want = json.loads((TESTDATA / "reduced.json").read_text())
    assert program_spans.reduce(str(path)) == want


def test_pinned_trace_names_both_threads():
    got = json.loads((TESTDATA / "reduced.json").read_text())
    assert sorted(t["role"] for t in got["program_threads"]) == \
        ["deal", "online"]
    assert len({t["line"] for t in got["program_threads"]}) == 2
    assert got["program_spans"]["online_spans"] == 3
    assert got["program_spans"]["prep_wait_spans"] == 3
    assert got["idle_s"] > 0
    assert any(":" in name for name, _ in got["idle_gaps"])


REDUCED = {
    "program_threads": [
        {"line": "/host:CPU#1", "role": "deal", "spans": 4, "span_s": 40.0,
         "jax_call_s": 30.0, "cpu_s": 28.0},
        {"line": "/host:CPU#2", "role": "online", "spans": 4,
         "span_s": 12.0, "jax_call_s": 8.0, "cpu_s": 9.0}],
    "program_spans": {"deal_s": 40.0, "deal_cpu_s": 28.0, "deal_spans": 4,
                      "online_s": 12.0, "online_cpu_s": 9.0,
                      "online_spans": 4, "prep_wait_s": 20.0,
                      "prep_wait_spans": 4},
}
READINGS = {
    "prep_wait_ms_per_batch.serve": 5000.0,
    "dealer_cpu_share.serve": 70.0,
    "online_cpu_share.serve": 75.0,
    "online_dispatch_ms_per_batch.serve": 2000.0,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_per_layer_reading(name):
    assert program_spans.per_layer(REDUCED)[name] == \
        pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(READINGS))
def test_per_layer_reads_nothing_without_program_spans(name):
    empty = {"program_threads": [], "program_spans": {}}
    assert program_spans.per_layer(empty)[name] is None


def test_segments_take_the_innermost_span():
    spans = [(0, 10, "a"), (2, 4, "b"), (3, 4, "c"), (6, 8, "d")]
    assert program_spans._segments(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"), (6, 8, "d"),
        (8, 10, "a")]


def _roles():
    I = program_spans._Intervals
    roles = {"deal": [], "online": []}
    inner = {"deal": I([(0, 100, "trident.deal")]),
             "online": I([(0, 40, "trident.prep.wait"),
                          (40, 100, "trident.serve.batch.online")])}
    in_call = {"deal": I([(10, 20), (50, 60)]),
               "online": I([(15, 25), (70, 80)])}
    return roles, inner, in_call, I([(0, 40)]), I([])


@pytest.mark.parametrize("t,want", [
    (17, [("serve.flush/deal:deal/dispatch", 0.5),
          ("serve.flush/online:prep.wait/dispatch", 0.5)]),
    (55, [("serve.flush/deal:deal/dispatch", 1.0)]),
    (75, [("serve.flush/online:serve.batch.online/dispatch", 1.0)]),
    (30, [("serve.flush/deal:deal/python", 1.0)]),
    (90, [("serve.flush/online:serve.batch.online/python", 1.0)]),
])
def test_idle_slice_goes_to_one_thread(t, want):
    roles, inner, in_call, waiting, any_call = _roles()
    harness = [("serve.flush", 0, 100)]
    assert program_spans._attribute(t, harness, roles, inner, in_call,
                                    waiting, any_call) == want


def test_idle_slice_without_program_threads_keeps_the_harness_name():
    I = program_spans._Intervals
    assert program_spans._attribute(
        5, [("serve.flush", 0, 10)], {}, {}, {}, I([]),
        I([(4, 6)])) == [("serve.flush/dispatch", 1.0)]


def test_attribute_rehearsal():
    import attribute
    attribute.harness.prepare_environment()
    from repro import obs
    args = argparse.Namespace(workload="nn_serve_b128", seed=2**31 + 11,
                              seconds=0.0, program_spans=1, rehearse=True)
    prev = obs.install_tracer(obs.NULL_TRACER)
    try:
        result = attribute.attribute(args)
    finally:
        obs.install_tracer(prev)
    assert result["correct"]
    assert sorted(t["role"] for t in result["program"]["program_threads"]) \
        == ["deal", "online"]
    assert all(v is not None for v in result["per_layer"].values()), \
        result["per_layer"]
