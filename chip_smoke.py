#!/usr/bin/env python3
"""Smoke test: the secure NN's training and inference on one TPU chip.

Drives the main path once, through the entry points a user calls, at the
full width of the paper's NN (784-128-128-10, batch 128), with weights
and data made from ``--seed``.  Every phase runs in this one process:

  f64     ``Ring.encode``/``decode`` on the device agree with numpy bit
          for bit at the magnitudes the NN uses;
  train   2 secure SGD steps on the party runtime (``RuntimeEngine`` over
          ``LocalTransport``), each bit-identical to the joint simulation
          of the same step, within a fixed-point tolerance of a float
          ``PlainEngine`` step from the same inputs, with no abort flag;
  serve   ``PartyPredictionServer(prep="pipelined")`` answers 3 batches
          of 128 predictions, each bit-identical to the joint simulation
          of the same (batch, seed), close to the float reference, with
          zero offline bytes on the serving path;
  pallas  one training step with ``kernel_backend="pallas"`` (kernels
          compiled by Mosaic), bit-identical to the jnp step.

``--four-chips`` runs only the four-chip phase: one training step through
``PartyCluster`` with party i on chip i (one process drives the four
chips, see CHANGES.md), then the same step in process, bit-identical.

Earlier lines report the device, set-up time (first call of a shape,
compilation included) apart from steady time, the loss, and the largest
error against the float reference.  The last line of stdout is
``{"ok": true, "device": {...}}``; a failed phase, or a JAX that finds no
TPU, exits non-zero without it.

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

BATCH = 128              # the paper's smallest NN batch
TRAIN_STEPS = 2
SERVE_BATCHES = 3
WARMUP_BATCHES = 1
CLUSTER_TIMEOUT = 420.0  # the first cluster task compiles on four chips


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def timed(fn, *args, **kw):
    """(fn's result, wall seconds); results here are host values, so the
    device work is finished when fn returns."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def tpu_devices(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devs)}")
    return devs


def tolerances():
    """Fixed-point tolerances against the float reference, in ulps of
    the ring's fixed point (2^-13).  Each secure matmul truncates once,
    and the 1/batch scaling of the output error leaves it a few ulps, so
    an update (lr times a sum over the batch of such terms) carries up to
    lr * batch ulps of rounding per weight."""
    from repro.core.ring import RING64
    from repro.train import secure_sgd as SGD
    ulp = 1.0 / RING64.scale
    lr = SGD.nn_task().lr
    return {"param": 8 * lr * BATCH * ulp, "loss": 8 * ulp,
            "prob": 64 * ulp}


def float_ref(fn, *args):
    """A float reference computation at full f32 matmul precision (the
    TPU's default passes f32 matmuls through bf16)."""
    import jax
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def max_abs_diff(a: dict, b: dict) -> float:
    return max(float(np.max(np.abs(np.asarray(a[k]) - np.asarray(b[k]))))
               for k in a)


def same_bits(a: dict, b: dict) -> bool:
    return sorted(a) == sorted(b) and all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------
def f64_phase(seed: int, arrays: list) -> None:
    """f64 on the device against numpy, over the NN's own inputs and
    weights plus a wider sample of the same magnitudes.  XLA:TPU emulates
    f64, so the device's own rounding is reported, and ``Ring.encode`` /
    ``decode`` (which run on the host for host values) must agree bit for
    bit."""
    import jax
    import jax.numpy as jnp
    from repro.core.ring import RING64 as R
    rng = np.random.RandomState(seed)
    vals = np.concatenate([np.ravel(a) for a in arrays]
                          + [rng.randn(1 << 16) * 8.0])
    want = np.round(vals * R.scale).astype(np.int64).astype(np.uint64)
    on_device = np.asarray(jax.jit(
        lambda x: jnp.round(x * R.scale).astype(jnp.int64).astype(
            jnp.uint64))(vals))
    bad_dev = int(np.sum(on_device != want))
    bad_enc = int(np.sum(np.asarray(R.encode(vals)) != want))
    dec = np.asarray(R.decode(jnp.asarray(want)))
    bad_dec = int(np.sum(dec != want.astype(np.int64) / R.scale))
    log(f"f64: {vals.size} values, |x| <= {np.max(np.abs(vals)):.3f}: "
        f"f64 rounding on the device differs from numpy for {bad_dev}; "
        f"Ring.encode mismatches {bad_enc}, Ring.decode mismatches "
        f"{bad_dec}")
    check(bad_enc == 0 and bad_dec == 0,
          "Ring.encode/decode disagree with numpy")


def train_phase(seed: int, tol: dict):
    """TRAIN_STEPS secure SGD steps; returns (initial params, batches,
    per-step runtime results)."""
    from repro.nn.engine import PlainEngine
    from repro.train import data as D
    from repro.train import secure_sgd as SGD
    task = SGD.nn_task()
    data = D.MNISTLike(seed=seed)
    params0 = task.init_params(seed)
    batches = [tuple(data.batch(t, BATCH)[:2]) for t in range(TRAIN_STEPS)]
    params, results = params0, []
    for t, batch in enumerate(batches):
        kind = "set-up, compiles" if t == 0 else "steady"
        (new, loss, abort), rt_s = timed(
            SGD.run_step, task, params, batch, step=t, base_seed=seed,
            world="runtime")
        (j_new, j_loss, j_abort), j_s = timed(
            SGD.run_step, task, params, batch, step=t, base_seed=seed,
            world="joint")
        (p_new, p_loss, _), p_s = timed(float_ref, task.run, PlainEngine(),
                                        params, batch)
        err, lerr = max_abs_diff(new, p_new), abs(loss - p_loss)
        log(f"train step {t} ({kind}): runtime {rt_s:.3f} s, joint "
            f"{j_s:.3f} s, float {p_s:.3f} s; loss {loss!r} (float "
            f"{p_loss!r}); max |param - float| {err:.6g} (tol "
            f"{tol['param']:.6g}), |loss - float| {lerr:.6g} (tol "
            f"{tol['loss']:.6g}); abort {abort}")
        check(not abort and not j_abort, f"step {t}: abort flag raised")
        check(same_bits(new, j_new) and loss == j_loss,
              f"step {t}: runtime (params, loss) != joint simulation")
        check(err <= tol["param"] and lerr <= tol["loss"],
              f"step {t}: secure step too far from the float step")
        results.append((new, loss))
        params = new
    return params0, batches, results


def _predict(rt, X, *, params, net):
    """One batch of secure NN predictions on the party runtime (the
    ``PartyPredictionServer`` contract: opened probabilities)."""
    from repro.nn.runtime_engine import RuntimeEngine
    from repro.train import paper_ml as PML
    eng = RuntimeEngine(rt)
    sh = {k: eng.from_plain(params[k]) for k in sorted(params)}
    return np.asarray(eng.to_plain(
        PML.mlp_net_predict(eng, sh, net, eng.from_plain(X))))


def _predict_joint(seed, X, params, net):
    from repro.core.context import make_context
    from repro.nn.engine import TridentEngine
    from repro.train import paper_ml as PML
    eng = TridentEngine(make_context(seed=seed), nonlinear="newton")
    sh = {k: eng.from_plain(params[k]) for k in sorted(params)}
    return np.asarray(eng.to_plain(
        PML.mlp_net_predict(eng, sh, net, eng.from_plain(X))))


def serve_phase(seed: int, params: dict, tol: dict) -> None:
    from repro.nn.engine import PlainEngine
    from repro.serve.party_server import PartyPredictionServer
    from repro.train import data as D
    from repro.train import paper_ml as PML
    from repro.train import secure_sgd as SGD
    net = SGD.nn_task().net
    data = D.MNISTLike(seed=seed + 1)
    queries = data.X[:(WARMUP_BATCHES + SERVE_BATCHES) * BATCH]
    warm = WARMUP_BATCHES * BATCH
    srv = PartyPredictionServer(
        functools.partial(_predict, params=params, net=net),
        batch_size=BATCH, seed=seed, prep="pipelined")
    try:
        # warm-up batch: set-up, the first call of every shape compiles
        for q in queries[:warm]:
            srv.submit(q)
        _, warm_s = timed(srv.flush)
        for q in queries[warm:]:
            srv.submit(q)
        preds, wall = timed(srv.flush)
        rep = srv.report()
    finally:
        srv.close()
    log(f"serve: warm-up batch (set-up, compiles) {warm_s:.3f} s; "
        f"{SERVE_BATCHES} batches of {BATCH} in {wall:.3f} s "
        f"({wall / SERVE_BATCHES:.3f} s per batch); report over all "
        f"{rep['batches']} batches: online-only "
        f"{rep['online_only_ms_per_batch']:.1f} ms/batch, dealer "
        f"{rep['offline_deal_s_per_batch']:.3f} s/batch, offline bits "
        f"online {rep['offline_bits_per_batch']}, aborted {rep['aborted']}")
    check(rep["offline_bits_per_batch"] == 0,
          "offline bytes on the serving path")
    check(not rep["aborted"], "serve: abort flag raised")
    plain = PlainEngine()
    for k in range(SERVE_BATCHES):
        X = queries[warm + k * BATCH:warm + (k + 1) * BATCH]
        got = np.stack(preds[k * BATCH:(k + 1) * BATCH])
        # the server deals batch b of its stream from seed + b
        want = _predict_joint(seed + WARMUP_BATCHES + k, X, params, net)
        ref = np.asarray(plain.to_plain(float_ref(
            PML.mlp_net_predict, plain,
            {n: plain.from_plain(w) for n, w in params.items()}, net,
            plain.from_plain(X))))
        err = float(np.max(np.abs(got - ref)))
        log(f"serve batch {k}: bit-identical to joint "
            f"{np.array_equal(got, want)}; max |prob - float| {err:.6g} "
            f"(tol {tol['prob']:.6g})")
        check(np.array_equal(got, want),
              f"serve batch {k} != joint simulation of the same seed")
        check(err <= tol["prob"], f"serve batch {k} too far from float")


def pallas_phase(seed: int, params0: dict, batch: tuple, want) -> None:
    from repro.train import secure_sgd as SGD
    task = SGD.nn_task()
    for kind in ("set-up, compiles", "steady"):
        (new, loss, abort), s = timed(
            SGD.run_step, task, params0, batch, step=0, base_seed=seed,
            world="runtime", kernel_backend="pallas")
        same = same_bits(new, want[0]) and loss == want[1]
        log(f"pallas step 0 ({kind}): {s:.3f} s; loss {loss!r}; "
            f"bit-identical to jnp {same}; abort {abort}")
        check(same and not abort,
              "pallas-backend step != jnp-backend step")


def four_chip_phase(seed: int) -> None:
    """Party i on chip i through PartyCluster, then the same step in this
    process."""
    from repro.runtime.net.cluster import PartyCluster
    from repro.train import data as D
    from repro.train import secure_sgd as SGD
    devs = tpu_devices(4)[:4]
    task = SGD.nn_task()
    params = task.init_params(seed)
    batch = tuple(D.MNISTLike(seed=seed).batch(0, BATCH)[:2])
    (cluster, boot_s) = timed(PartyCluster, devices=devs,
                              timeout=CLUSTER_TIMEOUT)
    with cluster:
        for rank, dev in sorted(cluster.daemon_devices.items()):
            log(f"four-chip: party daemon P{rank} computes on {dev}")
        check(len(set(cluster.daemon_devices.values())) == 4,
              "the four parties do not hold four distinct chips")
        sgd = SGD.ClusterSGD(cluster, task, base_seed=seed)
        (new, loss, abort), s = timed(sgd, params, 0, *batch)
    log(f"four-chip: cluster boot {boot_s:.3f} s; step 0 (set-up, "
        f"compiles on each chip) {s:.3f} s; loss {loss!r}; abort {abort}")
    (ref, ref_loss, ref_abort), rs = timed(
        SGD.run_step, task, params, batch, step=0, base_seed=seed,
        world="runtime")
    same = same_bits(new, ref) and loss == ref_loss
    log(f"four-chip: in-process step {rs:.3f} s; bit-identical to the "
        f"cluster step {same}")
    check(not abort and not ref_abort, "four-chip: abort flag raised")
    check(same, "four-chip cluster step != in-process step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip PartyCluster phase")
    args = ap.parse_args(argv)

    import jax
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        tpu_devices(1)
        dev = jax.devices()[0]
        log(f"device: {dev.platform} {dev.device_kind} "
            f"({len(jax.devices())} visible; this run uses one)")
        tol = tolerances()
        t0 = time.perf_counter()
        params0, batches, results = train_phase(args.seed, tol)
        f64_phase(args.seed, [*batches[0], *params0.values()])
        serve_phase(args.seed, results[-1][0], tol)
        pallas_phase(args.seed, params0, batches[0], results[0])
        log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
