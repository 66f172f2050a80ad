#!/usr/bin/env python3
"""Record the small chip trace that ``test_chipbench.py`` pins the trace
reduction on: inside the harness's window span, three ``train.step`` spans
each run a few ring matmuls (``core/ring.py``) with host sleeps between
them, so the trace has device busy time, idle gaps and named modules.

    python3 benchmarks/chipbench/record_testdata.py <out_dir>

writes the profile and ``reduced.json`` (the reduction's result) to
``<out_dir>``; copy both into ``testdata/``.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import run as harness


def main(out_dir: str) -> int:
    harness.prepare_environment()
    harness.check_devices(1, rehearse=False)
    import jax
    import jax.numpy as jnp
    import repro  # noqa: F401  (x64)
    from repro.core.ring import RING64
    import trace_reduce
    a = jnp.arange(512 * 784, dtype=jnp.uint64).reshape(512, 784) * 7919
    b = jnp.arange(784 * 980, dtype=jnp.uint64).reshape(784, 980) * 104729
    RING64.matmul(a, b).block_until_ready()          # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(out_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("train.step"):
                    for _ in range(2):
                        RING64.matmul(a, b).block_until_ready()
                        time.sleep(0.01)
                time.sleep(0.02)
    reduced = trace_reduce.reduce(trace_reduce.find_xplane(out_dir))
    path = pathlib.Path(out_dir) / "reduced.json"
    path.write_text(json.dumps(reduced, indent=1, sort_keys=True) + "\n")
    print(json.dumps(reduced["breakdown"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
