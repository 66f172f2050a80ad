#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json`` at the checkout's root: ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json`` here.  The run sets
up and warms up (``setup_s``), measures for ``--seconds``, compares what
the window produced with the plain reference (``compare.py``), and prints
one JSON line as the last line of stdout.  With ``--trace 1`` the window
runs under the JAX profiler and the line carries the per-layer metrics,
each read by ``metrics/<metric>.py``; with ``--trace 0`` it carries the
end-to-end metrics.

It runs on the first TPU chip and exits non-zero, printing no result,
where JAX finds none.  ``--rehearse`` runs on the CPU instead, at a tiny
width and batch, and prints no device metric.
"""
from __future__ import annotations

import time

CLOCK_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
REHEARSE_WIDTH = 16
REHEARSE_BATCH = 16


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


def rehearsal_sizes(config: dict, traffic: dict) -> tuple[dict, dict]:
    """The cell cut to a CPU rehearsal: hidden widths and batch 16."""
    layers = config["layers"]
    config = dict(config, layers=[min(w, REHEARSE_WIDTH)
                                  for w in layers[:-1]] + [layers[-1]])
    return config, dict(traffic, batch=min(traffic["batch"], REHEARSE_BATCH))


def read_metric(name: str, run: dict):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}",
        HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


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
    cell, config, traffic, limits = load_cell(args.workload)
    if args.rehearse:
        config, traffic = rehearsal_sizes(config, traffic)
    prepare_environment()
    import drivers
    import trace_reduce
    dev = check_devices(cell["chips"], args.rehearse)
    import repro  # noqa: F401  (x64, compile cache)
    from repro.core.ring import Ring
    import jax

    ring = Ring(ell=config["ring_ell"], frac=config["ring_frac"])
    if args.control:
        ring = Ring(**limits["control_ring"])
    log_dir = None
    if args.trace and not args.rehearse:
        log_dir = tempfile.mkdtemp(prefix="chipbench-")
    window = Window(log_dir)
    try:
        out = drivers.DRIVERS[traffic["driver"]](drivers.Cell(
            config=config, traffic=traffic, seed=args.seed,
            seconds=args.seconds, ring=ring, window=window,
            clock_start=CLOCK_START))
        reduced = (trace_reduce.reduce(trace_reduce.find_xplane(log_dir))
                   if log_dir is not None else None)
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)

    checks = {k: {"value": v, "limit": limits["limits"][k]}
              for k, v in out.numbers.items()}
    missing = sorted(set(limits["limits"]) - set(checks))
    import compare
    correct = (not missing and out.failed == 0 and compare.passed(checks))
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
        for m in cell["per_layer"]:
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
    elif not args.rehearse:
        values = dict(out.end_to_end, setup_s=out.setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    if args.rehearse:
        device = {"platform": dev.platform, "rehearsal": True}
    return {"correct": bool(correct), "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device,
            **extra, "window_s": out.window_s,
            "window_batches": out.layer["batches"],
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
