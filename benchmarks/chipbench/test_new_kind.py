"""A new model kind and a new driver kind join the benchmark as new files
and ``BENCHMARK.json`` entries only, and every cell names a model and a
driver that exist.

The new kinds are the fixture under ``testdata/new_kind/``, laid out as in
this directory: the paper's logistic-regression predictor (its
configuration and model module), served by the ``serve`` driver and by a
driver of its own, ``inline``, which reports an end-to-end metric of its
own; each cell with its traffic and limits.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chipbench
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import pathlib
import shutil
import sys

import pytest

import run as harness
from test_chipbench import rehearse

HERE = pathlib.Path(__file__).resolve().parent
FIXTURE = HERE / "testdata" / "new_kind"
MODEL_API = ("weights", "feed", "program", "reference", "numbers",
             "ops_per_sample", "rehearsal")
DRIVER_API = ("run", "faults")
BENCH = harness.read_benchmark()


def lacks(module, names) -> list:
    return [n for n in names if not callable(getattr(module, n, None))]


def tree(root: pathlib.Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_names_a_model(config):
    harness.prepare_environment()
    spec = next(c for c in BENCH["configs"] if c["name"] == config)
    model = json.loads((harness.ROOT / spec["file"]).read_text())["model"]
    assert not lacks(harness.plugin("models", model), MODEL_API)


@pytest.mark.parametrize("traffic", sorted(
    {w["traffic"] for w in BENCH["workloads"]}))
def test_traffic_names_a_driver(traffic):
    spec = json.loads((HERE / "traffic" / f"{traffic}.json").read_text())
    assert not lacks(harness.plugin("drivers", spec["driver"]), DRIVER_API)


def add_new_kind(root: pathlib.Path) -> list:
    """Add the fixture to the benchmark at ``root`` as new files and
    ``BENCHMARK.json`` entries; gives the new workloads' names."""
    bench_dir = root / "benchmarks" / "chipbench"
    for src in sorted(FIXTURE.rglob("*")):
        rel = src.relative_to(FIXTURE)
        if src.is_file() and len(rel.parts) > 1 \
                and "__pycache__" not in rel.parts:
            assert not (bench_dir / rel).exists(), rel
            shutil.copy(src, bench_dir / rel)
    entries = json.loads((FIXTURE / "BENCHMARK.entries.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, added in entries.items():
        bench[key] += added
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return [w["name"] for w in entries["workloads"]]


# the end-to-end metrics each new cell reports: the serve driver's, and the
# inline driver's own, scoped to its cell; each with the set-up time
REPORTS = {
    "logreg_serve_b128": {"serve_preds_per_s", "serve_online_ms_per_batch",
                          "setup_s"},
    "logreg_inline_b128": {"inline_preds_per_s", "setup_s"},
}


def test_new_kind_joins_by_new_files_only(tmp_path, monkeypatch):
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = tree(tmp_path)
    workloads = add_new_kind(tmp_path)
    assert sorted(workloads) == sorted(REPORTS)

    # the copy's harness: its own files, and the program of this checkout;
    # the compile cache directory that the copy sets is undone afterwards
    harness.prepare_environment()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(harness.ROOT / ".jax_cache"))
    monkeypatch.syspath_prepend(str(tmp_path / "benchmarks" / "chipbench"))
    spec = importlib.util.spec_from_file_location(
        "chipbench_copy_run", tmp_path / "benchmarks" / "chipbench" / "run.py")
    copy = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, copy)
    spec.loader.exec_module(copy)
    for workload in workloads:
        _, config, traffic, _ = copy.load_cell(workload)
        driver, model = copy.resolve(config, traffic)
        assert not lacks(model, MODEL_API) and not lacks(driver, DRIVER_API)

        # the rehearsal reports its end-to-end metrics through the branch
        # that a chip run takes (run.py ``end_to_end``)
        result = rehearse(workload, bench=copy)
        assert result["correct"], (workload, result["checks"])
        assert result["failed"] == 0 and result["attempted"] > 0
        assert set(result["metrics"]) == REPORTS[workload], workload
        with monkeypatch.context() as m:
            m.setattr(*driver.faults()["answer_altered"]())
            result = rehearse(workload, bench=copy)
        assert not result["correct"], (workload, result["checks"])

    after = tree(tmp_path)
    assert [p for p, h in before.items() if after.get(p) != h] \
        == [pathlib.Path("BENCHMARK.json")]
    old = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert {k: v[:len(old[k])] if isinstance(v, list) else v
            for k, v in new.items()} == old
