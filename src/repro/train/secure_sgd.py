"""Secure SGD across the three execution worlds, with per-step prep.

One engine-agnostic training step (the paper's Section VI workloads via
``paper_ml``) runs on:

  * ``world="joint"``   -- TridentEngine (joint simulation, newton
                           nonlinearities: the only route with a runtime
                           twin);
  * ``world="runtime"`` -- RuntimeEngine over a LocalTransport (or any
                           transport you pass), interleaved or
                           online-only from a PrepStore;
  * ``ClusterSGD``      -- each step one ``PartyCluster`` task across the
                           four socket daemons, optionally consuming
                           step-indexed PrepBank sessions (prep-ahead:
                           zero offline bytes on the mesh, enforced) --
                           or, with ``prep="live"`` +
                           ``attach_live_dealer``, sessions STREAMED into
                           the running daemons over the control channel,
                           so training is unbounded and the bank starts
                           empty.

Determinism contract: step t always runs from
``trainer.seed_for_step(base_seed, t)``; the dealer's session t uses the
same seed, so all three worlds -- and a checkpoint-restored replay of any
step -- produce bit-identical ``(params, loss)`` trajectories
(tests/test_runtime_train.py pins this, the acceptance criterion of the
RuntimeEngine refactor).

Params cross step boundaries as plaintext float64 trees (the fixed-point
decode/encode round-trip is exact for trained-weight magnitudes), so the
existing ``Trainer``/checkpoint machinery drives every world unchanged.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from ..core.context import make_context
from ..core.ring import RING64, Ring
from ..nn.engine import Engine, TridentEngine
from ..nn.runtime_engine import RuntimeEngine
from ..runtime import FourPartyRuntime
from . import paper_ml as PML
from .trainer import seed_for_step


def engine_abort(eng: Engine) -> bool:
    """The engine's malicious-check verdict (False for PlainEngine)."""
    rt = getattr(eng, "rt", None)
    if rt is not None:
        return bool(rt.abort_flag())
    ctx = getattr(eng, "ctx", None)
    if ctx is not None:
        return bool(ctx.abort_flag())
    return False


# ---------------------------------------------------------------------------
# The training step, written once against the Engine interface.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SGDTask:
    """One secure-SGD workload: which paper_ml step to drive and how.

    kind: "linreg" | "logreg" | "nn" (MLP with ReLU hidden + smx output).
    Picklable by design -- ``ClusterSGD`` ships it to the party daemons.
    """

    kind: str
    lr: float = 0.25
    features: int = 8
    net: PML.MLPNet | None = None

    def init_params(self, seed: int = 0) -> dict:
        rng = np.random.RandomState(seed)
        if self.kind == "nn":
            return PML.mlp_net_init(rng, self.net)
        return PML.reg_init(rng, self.features)

    def run(self, eng: Engine, params: dict, batch: tuple):
        """One fwd+bwd+SGD step; returns (new_params_np, loss, abort).
        ``params`` enter and leave as plaintext float64 trees; the loss is
        the declassified mean squared error (p - y), identical protocol
        trace in every world."""
        sh = {k: eng.from_plain(params[k]) for k in sorted(params)}
        if self.kind == "nn":
            X, onehot = batch[0], batch[1]
            new, p = PML.mlp_net_step(eng, sh, self.net, eng.from_plain(X),
                                      onehot, lr=self.lr)
            err = eng.add_public(p, -np.asarray(onehot, np.float64))
        else:
            step = PML.logreg_step if self.kind == "logreg" \
                else PML.linreg_step
            X, y = batch[0], batch[1]
            new, err = step(eng, sh, eng.from_plain(X), eng.from_plain(y),
                            lr=self.lr)
        sq = eng.mul(err, err)
        tot = eng.sum(sq, axis=tuple(range(len(eng.shape_of(sq)))))
        n = float(np.prod(eng.shape_of(sq)))
        loss = float(np.asarray(eng.to_plain(tot))) / n
        new_np = {k: np.asarray(eng.to_plain(new[k])) for k in sorted(new)}
        return new_np, loss, engine_abort(eng)


def logreg_task(features: int = 8, lr: float = 0.25) -> SGDTask:
    return SGDTask(kind="logreg", lr=lr, features=features)


def nn_task(net: PML.MLPNet | None = None, lr: float = 0.25) -> SGDTask:
    """The paper's NN benchmark net by default (784-128-128-10)."""
    if net is None:
        net = PML.MLPNet(features=784, layers=(128, 128, 10))
    return SGDTask(kind="nn", lr=lr, net=net)


# ---------------------------------------------------------------------------
# World runners (one step; step-indexed seeds).
# ---------------------------------------------------------------------------
def make_engine(world: str, seed: int, *, ring: Ring = RING64,
                transport=None, prep=None, kernel_backend=None) -> Engine:
    if world == "joint":
        return TridentEngine(make_context(ring, seed=seed),
                             nonlinear="newton")
    if world == "runtime":
        return RuntimeEngine(FourPartyRuntime(
            ring, seed=seed, transport=transport, prep=prep,
            kernel_backend=kernel_backend))
    raise ValueError(f"unknown world {world!r}")


def run_step(task: SGDTask, params: dict, batch: tuple, *, step: int,
             base_seed: int = 0, world: str = "joint", ring: Ring = RING64,
             transport=None, prep=None, kernel_backend=None):
    """One training step in `world` from the step-indexed seed
    (``kernel_backend`` picks the runtime world's local compute)."""
    eng = make_engine(world, seed_for_step(base_seed, step), ring=ring,
                      transport=transport, prep=prep,
                      kernel_backend=kernel_backend)
    return task.run(eng, params, batch)


def step_program(task: SGDTask, params: dict, batch: tuple):
    """The step as a runtime protocol program: ``program(rt)`` runs it on
    a RuntimeEngine over rt's transport/prep.  With zeroed inputs it is
    also the deal twin -- the offline half is data-independent, so the
    dealer walks the identical tag sequence."""

    def program(rt):
        return task.run(RuntimeEngine(rt), params, batch)

    return program


def zero_inputs(_task: SGDTask, params: dict, batch: tuple):
    """Shape-preserving zero (params, batch) for dealing ahead of data."""
    zp = {k: np.zeros_like(np.asarray(v, np.float64))
          for k, v in params.items()}
    zb = tuple(np.zeros_like(np.asarray(b, np.float64)) for b in batch)
    return zp, zb


def deal_step_program(task: SGDTask, params: dict, batch: tuple):
    """The data-independent dealer twin of ``step_program``."""
    zp, zb = zero_inputs(task, params, batch)
    return step_program(task, zp, zb)


# ---------------------------------------------------------------------------
# Prep-ahead training bank: session k == step k's offline material.
# ---------------------------------------------------------------------------
def deal_training_bank(task: SGDTask, params: dict, batch: tuple,
                       steps: int, *, base_seed: int = 0,
                       ring: Ring = RING64, path: str | None = None):
    """Deal one PrepStore per training step (seed = seed_for_step(base,
    k), matching what the online step k will trace) into a PrepBank;
    optionally serialize it for ``PartyCluster(prep_path=...)``.
    Returns (bank, [DealReport])."""
    from ..offline import deal_sessions
    program = deal_step_program(task, params, batch)
    bank, reports = deal_sessions([program] * steps, ring=ring,
                                  base_seed=base_seed,
                                  meta={"task": task.kind})
    if path is not None:
        bank.save(path)
    return bank, reports


class PrepAheadSGD:
    """Trainer step_fn over LocalTransport with per-step prep: each step
    pops its store (from a ContinuousDealer via ``store_for_step`` or a
    pre-dealt PrepBank) and executes ONLINE-ONLY -- the transport forbids
    offline traffic, so "zero offline bytes per training step" is
    wire-enforced, and the outputs are bit-identical to the interleaved
    step from the same seed."""

    def __init__(self, task: SGDTask, dealer, *, ring: Ring = RING64):
        self.task = task
        self.dealer = dealer            # ContinuousDealer (or compatible)
        self.ring = ring
        self.reports: list = []

    def step_fn(self, params, step, *batch):
        from ..offline import run_online
        store = self.dealer.store_for_step(step)
        program = step_program(self.task, params, tuple(batch))
        (new, loss, abort), report = run_online(program, store,
                                                ring=self.ring)
        self.reports.append(report)
        return new, loss, abort or report.abort

    __call__ = step_fn


# ---------------------------------------------------------------------------
# Distributed training: one PartyCluster task per step.
# ---------------------------------------------------------------------------
def _cluster_step_program(rt, _rank, task=None, params=None, batch=None):
    """Module-level (spawn-picklable) per-step program for the daemons."""
    eng = RuntimeEngine(rt)
    new, loss, abort = task.run(eng, params, batch)
    return {"params": new, "loss": loss, "abort": bool(abort)}


def _live_deal_program(rt, task=None, params=None, batch=None):
    """The dealer-daemon twin of ``_cluster_step_program``: same protocol
    trace from zeroed inputs (the offline half is data-independent)."""
    task.run(RuntimeEngine(rt), params, batch)


def _live_program_for_step(_step, *, task, params, batch):
    """Picklable ``step -> program`` for the ContinuousDealer inside the
    dealer daemon (every step traces the same shapes)."""
    return functools.partial(_live_deal_program, task=task, params=params,
                             batch=batch)


def attach_live_dealer(cluster, task: SGDTask, params: dict, batch: tuple,
                       *, base_seed: int = 0, ahead: int = 2,
                       total: int | None = None):
    """Start a ``DealerDaemon`` streaming step-indexed prep sessions into
    a LIVE cluster (built with ``live_prep=True``): session t is dealt
    from ``seed_for_step(base_seed, t)`` -- the same seed ``ClusterSGD``
    gives the online step t -- sliced per party, and shipped to daemon i
    over control queue i while earlier steps run online.  ``total=None``
    streams for as long as the training runs (open-ended).  Returns the
    daemon handle (a context manager; close it when training ends)."""
    from ..offline.live import DealerDaemon
    zp, zb = zero_inputs(task, params, batch)
    factory = functools.partial(_live_program_for_step, task=task,
                                params=zp, batch=zb)
    return DealerDaemon(cluster, factory, ring=cluster.ring,
                        base_seed=base_seed, ahead=ahead, total=total)


class ClusterSGD:
    """Trainer step_fn that drives a ``PartyCluster``: step t is one task
    across the four daemons, seeded ``seed_for_step(base_seed, t)`` so a
    checkpoint-restored replay regenerates the identical F_setup streams
    in every party process.

    ``prep="bank"`` makes every step consume its STEP-INDEXED PrepBank
    session (the daemons seek to session t, so resumed runs skip spent
    sessions and a retried step raises PrepReplayError naming it) and run
    online-only on the mesh -- zero offline bytes, transport-enforced.

    ``prep="live"`` is the same online-only consumption against a LIVE
    bank: the cluster was built with ``live_prep=True`` and an
    ``attach_live_dealer`` daemon streams session t's material over the
    control channel while step t-1 runs online, so the bank may start
    EMPTY and training is unbounded (no up-front ``deal_training_bank``).
    A step whose session has not arrived yet blocks in the daemons until
    the dealer catches up (or fails with the dealer's traceback).
    """

    PREPPED = ("bank", "live")

    def __init__(self, cluster, task: SGDTask, *, base_seed: int = 0,
                 prep: str | None = None, dealer=None):
        assert prep in (None, "bank", "live"), prep
        if prep == "live" and not getattr(cluster, "live_prep", False):
            raise ValueError("prep='live' needs a cluster built with "
                             "PartyCluster(live_prep=True)")
        self.cluster = cluster
        self.task = task
        self.base_seed = base_seed
        self.prep = prep
        # DealerDaemon (prep="live"): health() folds in the dealer's view
        self.dealer = dealer
        self.results: list = []         # per-step [PartyResult x4]

    def step_fn(self, params, step, *batch):
        program = functools.partial(
            _cluster_step_program, task=self.task,
            params={k: np.asarray(v) for k, v in params.items()},
            batch=tuple(np.asarray(b) for b in batch))
        results = self.cluster.submit(
            program, seed=seed_for_step(self.base_seed, step),
            prep="bank" if self.prep in self.PREPPED else None,
            prep_session=step if self.prep in self.PREPPED else None)
        ref = results[0].result
        for r in results[1:]:
            for k in ref["params"]:
                if not np.array_equal(r.result["params"][k],
                                      ref["params"][k]):
                    raise RuntimeError(
                        f"cluster divergence at step {step}: P{r.rank} "
                        f"params[{k!r}] differs from P0")
        self.results.append(results)
        abort = bool(ref["abort"]) or any(r.abort for r in results)
        return ref["params"], float(ref["loss"]), abort

    __call__ = step_fn

    def offline_bits_on_mesh(self) -> int:
        """Total offline-phase bits the socket mesh carried across the
        recorded steps (0 in prep="bank" mode -- the acceptance check)."""
        return sum(res[0].totals["offline"]["bits"] for res in self.results)

    def health(self, **kw) -> dict:
        """One cluster health document mid-training: all four party
        exporters plus the attached dealer's (``PartyCluster`` and
        ``DealerDaemon`` built with ``metrics=True``)."""
        return self.cluster.health(dealer=self.dealer, **kw)


# ---------------------------------------------------------------------------
# Data-parallel secure SGD: the global batch sharded across a cluster pool.
# ---------------------------------------------------------------------------
def shard_batch(batch: tuple, shards: int) -> list:
    """Split every batch array into ``shards`` EQUAL row-shards.  Equal
    sizes are required: each member's step normalizes its gradient by its
    shard size, so the mean of the members' updates equals the full-batch
    update only when the shards weigh the same."""
    arrays = tuple(np.asarray(b) for b in batch)
    n = arrays[0].shape[0]
    if n % shards:
        raise ValueError(
            f"global batch of {n} rows does not shard evenly across "
            f"{shards} pool members")
    step = n // shards
    return [tuple(a[i * step:(i + 1) * step] for a in arrays)
            for i in range(shards)]


class ShardedClusterSGD:
    """Data-parallel ``Trainer`` step_fn over a POOL of party clusters:
    step t splits the global batch into one equal shard per member, every
    member runs the step on its shard CONCURRENTLY (``submit_nowait`` on
    all members, then collect -- member k+1 executes while member k's
    results are gathered), and the new parameters aggregate as the mean
    across members.

    The aggregation is the secure FedAvg mean: since each member's step
    computes ``params - lr * grad_i`` with ``grad_i`` already normalized
    by the (equal) shard size,

        mean_i(params - lr * grad_i)  ==  params - lr * mean_i(grad_i),

    i.e. ONE linear combination of the members' outputs -- free on the
    wire in-protocol (lincombs move no bytes).  This runtime's step
    contract declassifies params at every step boundary (plaintext
    float64 trees, same as ``ClusterSGD``), so the mean is applied to the
    declassified updates here; a deployment keeps the updates as shares
    and applies the identical lincomb before any declassification.

    Every member runs from the SAME ``seed_for_step(base_seed, t)`` --
    members own independent meshes, so equal seeds just make each
    member's trajectory self-consistent and replayable.
    """

    def __init__(self, clusters, task: SGDTask, *, base_seed: int = 0):
        clusters = list(clusters)
        if not clusters:
            raise ValueError("ShardedClusterSGD needs at least one cluster")
        self.clusters = clusters
        self.task = task
        self.base_seed = base_seed
        self.results: list = []         # per-step [member -> [PartyResult x4]]

    def step_fn(self, params, step, *batch):
        params_np = {k: np.asarray(v) for k, v in params.items()}
        shards = shard_batch(tuple(batch), len(self.clusters))
        seed = seed_for_step(self.base_seed, step)
        handles = [
            cluster.submit_nowait(
                functools.partial(_cluster_step_program, task=self.task,
                                  params=params_np, batch=shard),
                seed=seed)
            for cluster, shard in zip(self.clusters, shards)]
        per_member = [cluster.collect(h)
                      for cluster, h in zip(self.clusters, handles)]
        news, losses, abort = [], [], False
        for m, results in enumerate(per_member):
            ref = results[0].result
            for r in results[1:]:
                for k in ref["params"]:
                    if not np.array_equal(r.result["params"][k],
                                          ref["params"][k]):
                        raise RuntimeError(
                            f"cluster divergence at step {step}, member "
                            f"{m}: P{r.rank} params[{k!r}] differs from P0")
            news.append(ref["params"])
            losses.append(float(ref["loss"]))
            abort = abort or bool(ref["abort"]) \
                or any(r.abort for r in results)
        self.results.append(per_member)
        mean = {k: np.mean([nw[k] for nw in news], axis=0)
                for k in sorted(news[0])}
        return mean, float(np.mean(losses)), abort

    __call__ = step_fn

    def offline_bits_on_mesh(self) -> int:
        """Total offline-phase bits across every member's mesh."""
        return sum(res[0].totals["offline"]["bits"]
                   for step in self.results for res in step)

    def health(self, **kw) -> dict:
        """Per-member cluster health documents, keyed by member index."""
        return {str(m): c.health(**kw)
                for m, c in enumerate(self.clusters)}
