#!/usr/bin/env python3
"""Record the small two-thread chip trace that ``test_program_spans.py``
pins ``program_spans.py`` on: with the program's tracer on the profiler
sink, a pipelined ``PartyPredictionServer`` at tiny widths streams three
batches through one ``flush()`` inside the harness's window and a
``serve.flush`` span, so the trace has a dealer thread under
``trident.deal``, an online thread under ``trident.serve.batch.online``
and ``trident.prep.wait``, and the device's idle gaps between them.

    python3 benchmarks/chipbench/record_threads_testdata.py <out_dir>

writes the profile, a gzipped copy of its ``.xplane.pb`` and
``reduced.json`` (the reduction's result) to ``<out_dir>``; copy the
last two into ``testdata_threads/``.
"""
from __future__ import annotations

import gzip
import json
import pathlib
import sys

import numpy as np

import run as harness

BATCH, FEATURES, WIDTH, BATCHES = 4, 8, 4, 3


def predict(rt, X):
    from repro.core.ring import RING64
    from repro.runtime import protocols as RT
    w = np.linspace(-1.0, 1.0, FEATURES * WIDTH).reshape(FEATURES, WIDTH)
    xs = RT.share(rt, RING64.encode(X))
    ws = RT.share(rt, RING64.encode(w))
    return RING64.decode(RT.reconstruct(rt, RT.matmul_tr(rt, xs, ws))[1])


def stream(srv, seed: int) -> None:
    for q in np.random.RandomState(seed).randn(BATCH * BATCHES, FEATURES):
        srv.submit(q)
    srv.flush()


def main(out_dir: str) -> int:
    harness.prepare_environment()
    harness.check_devices(1, rehearse=False)
    import jax
    from repro import obs
    from repro.serve.party_server import PartyPredictionServer
    import program_spans
    import trace_reduce
    obs.install_tracer(obs.Tracer("chipbench", sink="profiler"))
    srv = PartyPredictionServer(predict, batch_size=BATCH, seed=1,
                                prep="pipelined", prep_capacity=1)
    try:
        stream(srv, 0)                  # every program compiled outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(out_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                with jax.profiler.TraceAnnotation("serve.flush"):
                    stream(srv, 1)
    finally:
        srv.close()
    xplane = pathlib.Path(trace_reduce.find_xplane(out_dir))
    reduced = program_spans.reduce(str(xplane))
    out = pathlib.Path(out_dir)
    (out / f"{xplane.name}.gz").write_bytes(
        gzip.compress(xplane.read_bytes(), 9))
    (out / "reduced.json").write_text(
        json.dumps(reduced, indent=1, sort_keys=True) + "\n")
    print(json.dumps(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
