"""SocketTransport: four OS processes over TCP vs the in-process backends.

The acceptance contract of the distributed transport subsystem: a full NN
secure inference (share -> linear layers with fused truncation -> ReLU /
sigmoid via the ported conversions -> reconstruct) produces bit-identical
outputs on LocalTransport, SocketTransport (four processes), and the joint
simulation, with identical measured byte/round accounting -- and a
tampered TCP message still flips the abort flag.

The cluster launches are the expensive part (a JAX import per process), so
the honest run is module-scoped and shared across assertions.
"""
import numpy as np
import pytest

from repro.core import activations as ACT
from repro.core import protocols as PR
from repro.core.context import make_context
from repro.core.ring import RING64
from repro.runtime import FourPartyRuntime
from repro.runtime import activations as RA
from repro.runtime import protocols as RT
from repro.runtime.net import LAN, WAN, run_four_parties

SEED = 11
_rng = np.random.RandomState(0)
W1 = _rng.randn(4, 3) * 0.4
W2 = _rng.randn(3, 2) * 0.4
X = _rng.randn(2, 4)


def nn_program(rt, rank):
    """The acceptance-criteria NN: linear (fused trunc) -> relu -> linear
    -> sigmoid -> reconstruct.  Module-level so spawn can import it."""
    enc = RING64.encode
    xs = RT.share(rt, enc(X))
    w1 = RT.share(rt, enc(W1))
    w2 = RT.share(rt, enc(W2))
    h = RA.relu(rt, RT.matmul_tr(rt, xs, w1))
    out = RA.sigmoid(rt, RT.matmul_tr(rt, h, w2))
    opened = RT.reconstruct(rt, out)
    return np.asarray(opened[rank])


def local_reference():
    rt = FourPartyRuntime(RING64, seed=SEED)
    out = nn_program(rt, 1)
    return rt, out


@pytest.fixture(scope="module")
def socket_run():
    return run_four_parties(nn_program, seed=SEED, timeout=300,
                            net_model=WAN)


class TestSocketEqualsLocal:
    def test_bit_identical_across_three_backends(self, socket_run):
        rt, local_out = local_reference()
        # joint simulation (same program order as nn_program, so the PRF
        # counter streams line up exactly)
        ctx = make_context(RING64, seed=SEED)
        enc = RING64.encode
        xs, w1, w2 = (PR.share(ctx, enc(a)) for a in (X, W1, W2))
        h = ACT.relu(ctx, PR.matmul_tr(ctx, xs, w1))
        out = ACT.sigmoid(ctx, PR.matmul_tr(ctx, h, w2))
        joint_out = np.asarray(PR.reconstruct(ctx, out))
        assert np.array_equal(local_out, joint_out)
        for res in socket_run:
            assert np.array_equal(res.result, joint_out), f"P{res.rank}"
        assert rt.transport.totals() == ctx.tally.totals()

    def test_measured_traffic_matches_local(self, socket_run):
        rt, _ = local_reference()
        want_totals = rt.transport.totals()
        want_links = rt.transport.per_link()
        for res in socket_run:
            assert res.totals == want_totals, f"P{res.rank}"
            assert res.per_link == want_links, f"P{res.rank}"

    def test_honest_run_does_not_abort(self, socket_run):
        assert not any(res.abort for res in socket_run)

    def test_wan_model_reports_round_dominated_time(self, socket_run):
        """The WAN network model over the socket backend: modeled online
        time is dominated by the rtt term, as the paper predicts."""
        res = socket_run[0]
        assert res.modeled_s is not None
        rounds = res.totals["online"]["rounds"]
        bits = res.totals["online"]["bits"]
        rtt_term = rounds * WAN.default.rtt_s
        bw_term = bits / WAN.default.bandwidth_bps
        assert res.modeled_s["online"] >= rtt_term > 10 * bw_term
        # and the LAN preset would be bandwidth-cheap in absolute terms
        assert LAN.seconds_for(rounds, bits) < 0.1 * res.modeled_s["online"]


class TestBatchedFraming:
    def test_round_coalescing_bounds_frames(self, socket_run):
        """All messages a (link, round) carries ride one frame: a party's
        frame count is bounded by links x rounds, far below its message
        count (jmp payloads + hash copies + per-piece sends)."""
        res = socket_run[0]
        frames = sum(res.frames_sent.values())
        rounds = res.totals["offline"]["rounds"] \
            + res.totals["online"]["rounds"]
        assert frames > 0
        # <= one frame per link per round (+ slack for flush-on-recv
        # splitting a round's sends around a blocking receive)
        assert frames <= 3 * rounds + 3, (frames, rounds)

    def test_byte_accounting_unchanged_by_coalescing(self, socket_run):
        """Framing is transport metadata: per-tag bit accounting must be
        identical to the unbatched LocalTransport."""
        rt, _ = local_reference()
        assert socket_run[0].per_link == rt.transport.per_link()


class TestClusterReuse:
    def test_long_lived_daemons_serve_multiple_tasks(self):
        """One mesh, two submitted programs: per-task deltas agree with a
        fresh one-shot run (the ROADMAP's long-lived party daemons)."""
        from repro.runtime.net import PartyCluster
        with PartyCluster(ring=RING64, timeout=300) as cluster:
            a = cluster.submit(nn_program, seed=SEED)
            b = cluster.submit(nn_program, seed=SEED)
            assert cluster.tasks_run == 2
        rt, local_out = local_reference()
        for res in (a[1], b[1]):
            assert np.array_equal(res.result, local_out)
        assert a[0].totals == b[0].totals == rt.transport.totals()


class TestSocketFaultInjection:
    def test_tampered_tcp_message_aborts(self):
        """Corrupt one gamma piece on P0's outgoing wire: the receiving
        process's hash cross-check must flip its abort flag."""
        res = run_four_parties(
            nn_program, seed=SEED, timeout=300,
            tampers=[{"src": 0, "tag": ".g2", "delta": 5}])
        assert any(r.abort for r in res)


def serve_predict(rt, Xb):
    """Module-level predict_fn for serve_over_sockets (spawn pickling)."""
    xs = RT.share(rt, RING64.encode(Xb))
    w = RT.share(rt, RING64.encode(W1))
    out = RA.relu(rt, RT.matmul_tr(rt, xs, w))
    return RING64.decode(RT.reconstruct(rt, out)[1])


class TestServeOverSockets:
    def test_query_stream_served_across_processes(self):
        from repro.serve.party_server import serve_over_sockets
        queries = np.random.RandomState(1).randn(6, 4)
        preds, report = serve_over_sockets(serve_predict, queries,
                                           batch_size=4, seed=3,
                                           timeout=300)
        assert len(preds) == len(queries)
        assert report["batches"] == 2 and not report["aborted"]
        ref = np.maximum(queries @ W1, 0.0)
        got = np.stack([np.asarray(p) for p in preds])
        assert np.abs(got - ref).max() < 0.02


class TestDaemonDevices:
    """Every party daemon reports the device it computes on, and none
    computes on a CPU it was not asked to use."""

    def test_cpu_refused_unless_asked_for(self, monkeypatch):
        from repro.runtime.net.cluster import boot_device
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert boot_device("P0").platform == "cpu"
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(RuntimeError, match="initialized a cpu backend"):
            boot_device("party daemon P0")

    def test_pinned_threads_match_local(self):
        """devices= runs the parties as threads pinned to the given
        devices (one process driving every chip): same mesh, same
        bits as the in-process runtime."""
        import jax
        from repro.runtime.net import PartyCluster
        dev = jax.devices()[0]
        _, local_out = local_reference()
        with PartyCluster(devices=[dev] * 4, timeout=300) as cluster:
            results = cluster.submit(nn_program, seed=SEED)
            assert cluster.daemon_devices == {
                r: f"{dev.platform}:{dev.id} ({dev.device_kind})"
                for r in range(4)}
        for r in results:
            assert np.array_equal(np.asarray(r.result), local_out)
            assert not r.abort
        with pytest.raises(ValueError, match="one per party"):
            PartyCluster(devices=[dev])
