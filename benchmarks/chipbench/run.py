#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json`` at the checkout's root: ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json`` here.  The
configuration's ``"model"`` names ``models/<model>.py`` and the traffic's
``"driver"`` names ``drivers/<driver>.py`` (``open_cell``).  The driver sets
up and warms up (``setup_s``), measures for ``--seconds`` and compares
what the window produced with the model's plain reference; the numbers
are held to their limits (``compare.py``), and the run prints one JSON
line as the last line of stdout.  With ``--trace 1`` the window
runs under the JAX profiler and the line carries the per-layer metrics,
each read by ``metrics/<metric>.py``; with ``--trace 0`` it carries the
end-to-end metrics that the cell's driver measures (``end_to_end``).

It runs on the first TPU chip and exits non-zero, printing no result,
where JAX finds none.  ``--rehearse`` runs on the CPU instead, at the
model's tiny cut (``rehearsal``), and prints no device metric: only
counts and host-clock CPU timings.
"""
from __future__ import annotations

import time

CLOCK_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class BenchError(RuntimeError):
    pass


def read_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def read_limits(name: str) -> dict:
    return json.loads((HERE / "limits" / f"{name}.json").read_text())


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(cell, configuration, traffic, limits) of the cell ``name``."""
    bench = read_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = read_limits(name)
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    cell = dict(cell, per_layer=per_layer, end_to_end=end_to_end)
    return cell, config, traffic, limits


def plugin(kind: str, name: str):
    """The module ``<kind>/<name>.py`` here: a driver, a model or a
    metric's reader, found by its name and nothing else."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind}/{name}.py in {HERE}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(config: dict, traffic: dict):
    """(driver, model): the modules that the traffic's ``"driver"`` and
    the configuration's ``"model"`` name.  Call it once the environment is
    prepared: a model may import JAX."""
    return (plugin("drivers", traffic["driver"]),
            plugin("models", config["model"]))


def read_metric(name: str, run: dict):
    return plugin("metrics", name).read(run)


def prepare_environment() -> None:
    """Before JAX starts: the compile cache lives in the checkout, at a
    fixed path, and the TPU runtime writes no logs (it would write them to
    a fixed path under /tmp).  ``LIBTPU_INIT_ARGS`` is left as it is."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def check_devices(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    if rehearse:
        return devs[0]
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[0]


@dataclasses.dataclass
class Opened:
    """A cell ready to run: its ``BENCHMARK.json`` entry (``spec``), its
    configuration and traffic (cut by the model's ``rehearsal`` in a
    rehearsal), its limits, its driver and model, and the device."""
    spec: dict
    config: dict
    traffic: dict
    limits: dict
    driver: types.ModuleType
    model: types.ModuleType
    device: object

    def program_ring(self):
        from repro.core.ring import Ring
        return Ring(ell=self.config["ring_ell"],
                    frac=self.config["ring_frac"])

    def run(self, seed: int, seconds: float, window, ring=None,
            clock_start: float = CLOCK_START):
        """One run of the driver: its ``Outcome``."""
        import common
        return self.driver.run(common.Cell(
            config=self.config, traffic=self.traffic, model=self.model,
            seed=seed, seconds=seconds,
            ring=self.program_ring() if ring is None else ring,
            window=window, clock_start=clock_start))

    def judge(self, out) -> tuple[bool, dict, list]:
        """(correct, each limit's number beside it, the limits the run gave
        no number for)."""
        import compare
        checks, missing = compare.checks(out.numbers, self.limits)
        correct = not missing and out.failed == 0 and compare.passed(checks)
        return bool(correct), checks, missing


def open_cell(workload: str, rehearse: bool) -> Opened:
    """Find the cell, its driver and its model by name, prepare the
    environment before JAX starts, and check the devices."""
    spec, config, traffic, limits = load_cell(workload)
    prepare_environment()
    driver, model = resolve(config, traffic)
    if rehearse:
        config, traffic = model.rehearsal(config, traffic)
    device = check_devices(spec["chips"], rehearse)
    return Opened(spec, config, traffic, limits, driver, model, device)


def end_to_end(spec: dict, out, rehearse: bool) -> dict:
    """The cell's end-to-end metrics as its driver measured them: every
    entry that names the cell under ``workloads``, and every entry with no
    ``workloads`` that the driver measures (``Outcome.end_to_end``, and
    ``setup_s``), so a driver of another kind reports its own.  A
    rehearsal reports the host-clock ones: CPU timings, never a device's."""
    values = dict(out.end_to_end, setup_s=out.setup_s)
    metrics = {}
    for m in spec["end_to_end"]:
        if rehearse and m["source"] != "host_clock":
            continue
        if m["name"] in values:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        elif "workloads" in m:
            raise BenchError(f"the driver measured no {m['name']!r}, which "
                             "BENCHMARK.json names for this cell")
    return metrics


class Window:
    """Wraps the timed window: counts the programs JAX compiles or loads
    from its cache inside it (a steady window has none), and runs it under
    the profiler when given a directory."""

    COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self, log_dir: str | None):
        import jax
        self.log_dir = log_dir
        self.requests = 0
        self.compiles = None
        self._stack = contextlib.ExitStack()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.COMPILE_EVENT:
            self.requests += 1

    def __enter__(self):
        import jax
        import trace_reduce
        if self.log_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self._stack.enter_context(
                jax.profiler.trace(self.log_dir, profiler_options=opts))
            self._stack.enter_context(
                jax.profiler.TraceAnnotation(trace_reduce.WINDOW))
        self._start = self.requests
        return self

    def __exit__(self, *exc):
        self.compiles = self.requests - self._start
        return self._stack.__exit__(*exc)


def run(args) -> dict:
    cell = open_cell(args.workload, args.rehearse)
    import trace_reduce
    import repro  # noqa: F401  (x64, compile cache)
    import jax

    dev = cell.device
    ring = None
    if args.control:
        from repro.core.ring import Ring
        ring = Ring(**cell.limits["control_ring"])
    log_dir = None
    if args.trace and not args.rehearse:
        log_dir = tempfile.mkdtemp(prefix="chipbench-")
    window = Window(log_dir)
    try:
        out = cell.run(args.seed, args.seconds, window, ring=ring)
        reduced = (trace_reduce.reduce(trace_reduce.find_xplane(log_dir))
                   if log_dir is not None else None)
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)

    correct, checks, missing = cell.judge(out)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out.memory_peak_bytes}
    metrics, extra = {}, {}
    if args.trace:
        peaks = json.loads((HERE / "peaks.json").read_text())
        if dev.device_kind not in peaks and not args.rehearse:
            raise BenchError(f"no peaks for device {dev.device_kind!r} in "
                             "peaks.json")
        layer = dict(out.layer, trace=reduced, peak_ops_per_s=peaks.get(
            dev.device_kind, {}).get("int8_ops_per_s"))
        for m in cell.spec["per_layer"]:
            # a rehearsal reads counts only: no time, rate or share
            if args.rehearse and m["source"] != "program_counter":
                continue
            v = read_metric(m["name"], layer)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if not args.rehearse:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            extra["breakdown"] = reduced["breakdown"]
    else:
        metrics = end_to_end(cell.spec, out, args.rehearse)
    if args.rehearse:
        device = {"platform": dev.platform, "rehearsal": True}
    return {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device,
            **extra, "window_s": out.window_s,
            "window_batches": out.layer.get("batches"),
            "compiles_in_window": window.compiles,
            "missing_checks": missing, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny size (no device metric)")
    ap.add_argument("--control", action="store_true",
                    help="run the program on the control's lower-precision "
                         "ring (limits/<cell>.json); it must fail")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    try:
        result = run(args)
    except BenchError as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
