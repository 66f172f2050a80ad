"""The observability plane: tracer mechanics in both sinks (the buffer
and the JAX profiler's trace), span attribution, merging, metrics, the
serving stream's prep wait and thread CPU, and the traced 4-process
cluster path.
"""
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.runtime import FourPartyRuntime, LocalTransport
from repro.runtime import activations as ACT
from repro.runtime import protocols as RT


@pytest.fixture
def tracer():
    """Install a fresh enabled Tracer for the test, restore after."""
    tr = obs.Tracer("test")
    prev = obs.install_tracer(tr)
    try:
        yield tr
    finally:
        obs.install_tracer(prev)


def _program(rt):
    x = RT.share(rt, jnp.arange(6, dtype=jnp.int64).reshape(2, 3))
    y = RT.share(rt, jnp.ones((3, 2), dtype=jnp.int64))
    z = RT.matmul(rt, x, y)
    r = ACT.relu(rt, z)
    return RT.reconstruct(rt, r)[0]


# ---------------------------------------------------------------------------
# Off-by-default.
# ---------------------------------------------------------------------------
def test_tracing_off_by_default(monkeypatch):
    monkeypatch.delenv(obs.TRACE_ENV, raising=False)
    prev = obs.install_tracer(None)     # reset the lazy singleton
    try:
        assert obs.get_tracer() is obs.NULL_TRACER
        rt = FourPartyRuntime()
        assert not rt.tracer.enabled
        assert rt.transport.tracer is obs.NULL_TRACER
        _program(rt)                    # protocols run untraced
        assert obs.NULL_TRACER.drain() is None
    finally:
        obs.install_tracer(prev)


def test_env_var_enables(monkeypatch):
    monkeypatch.setenv(obs.TRACE_ENV, "1")
    prev = obs.install_tracer(None)
    try:
        assert obs.get_tracer().enabled
    finally:
        obs.install_tracer(prev)


# ---------------------------------------------------------------------------
# Spans.
# ---------------------------------------------------------------------------
def test_drain_resets_and_is_json_clean(tracer):
    rt = FourPartyRuntime(seed=1)
    _program(rt)
    chunk = tracer.drain()
    assert chunk["label"] == "test"
    assert chunk["events"], "no events traced"
    import json
    json.dumps(chunk)                    # plain data end to end
    # drained: the next chunk starts empty
    again = tracer.drain()
    assert again["events"] == []


def test_span_taxonomy_covers_all_layers(tracer):
    rt = FourPartyRuntime(seed=2)
    _program(rt)
    cats = {e["cat"] for e in tracer.drain()["events"]}
    for expected in ("protocol", "wire.round", "wire.send", "kernel"):
        assert expected in cats, (expected, cats)


def test_protocol_spans_carry_prep_and_check_attribution(tracer):
    rt = FourPartyRuntime(seed=3)
    _program(rt)
    spans = [e for e in tracer.drain()["events"]
             if e["cat"] == "protocol"]
    names = {e["name"] for e in spans}
    assert {"share", "matmul", "relu", "reconstruct"} <= names
    mm = next(e for e in spans if e["name"] == "matmul")
    assert mm["args"]["prep"] == "inline"
    assert mm["args"]["checks"] > 0      # malicious checks recorded


def test_round_spans_carry_phase_index_bits(tracer):
    rt = FourPartyRuntime(seed=4)
    _program(rt)
    rounds = [e for e in tracer.drain()["events"]
              if e["cat"] == "wire.round"]
    assert rounds
    online = [e for e in rounds if e["args"]["phase"] == "online"]
    assert [e["args"]["index"] for e in online] == list(range(len(online)))
    assert all(e["args"]["bits"] > 0 for e in rounds)
    # every analytic round has at least one traced scope; spans can
    # exceed the analytic count because parallel-overlapped scopes
    # max-merge in the tally but each emits its own span
    per_phase = {p: sum(1 for e in rounds if e["args"]["phase"] == p)
                 for p in ("offline", "online")}
    for p in ("offline", "online"):
        assert per_phase[p] >= rt.transport.rounds[p], (p, per_phase)


# ---------------------------------------------------------------------------
# Merging + metrics.
# ---------------------------------------------------------------------------
def _chunk(label, rank, epoch, events):
    return {"label": label, "rank": rank, "epoch": epoch,
            "events": events}


def test_merge_aligns_clocks_across_processes():
    # same absolute instant, different perf_counter origins
    a = _chunk("A", 0, 100.0, [{"ph": "i", "name": "x", "cat": "c",
                                "ts": 5.0}])
    b = _chunk("B", 1, 90.0, [{"ph": "i", "name": "y", "cat": "c",
                               "ts": 15.0}])
    doc = obs.merge_chunks([a, b, None])
    evs = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert {e["ts"] for e in evs} == {0.0}      # both at t=0, aligned
    assert doc["metadata"]["ranks"] == [0, 1]
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert names == {"A", "B"}


def test_merge_spans_use_microseconds():
    a = _chunk("A", 0, 0.0, [{"ph": "X", "name": "s", "cat": "c",
                              "ts": 1.0, "dur": 0.002}])
    doc = obs.merge_chunks([a])
    span = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert span["dur"] == pytest.approx(2000.0)


def test_metrics_snapshot(tracer):
    rt = FourPartyRuntime(seed=5)
    _program(rt)
    tracer.counter("depth", 3)
    tracer.counter("depth", 1)
    doc = obs.merge_chunks([tracer.drain()])
    snap = obs.metrics_snapshot(doc)
    assert snap["rounds"]["online"]["count"] == rt.transport.rounds["online"]
    assert snap["rounds"]["online"]["wall_ms"] > 0
    assert snap["sends"]["online"]["bits"] == \
        rt.transport.phase_bits["online"]
    assert snap["spans"]["protocol"]["count"] >= 4
    hist = snap["spans"]["protocol"]["hist"]
    assert sum(hist["counts"]) == snap["spans"]["protocol"]["count"]
    assert snap["counters"]["depth"] == {"last": 1, "max": 3}


def test_round_wall_ms(tracer):
    rt = FourPartyRuntime(seed=6)
    _program(rt)
    doc = obs.merge_chunks([tracer.drain()])
    walls = obs.round_wall_ms(doc)
    (pid,) = walls.keys()
    assert walls[pid]["online"] > 0


# ---------------------------------------------------------------------------
# The timed/stopwatch consolidation helpers (serve-layer bookkeeping).
# ---------------------------------------------------------------------------
class _Stats:
    compute_s = 0.0
    online_compute_s = 0.0


def test_timed_accumulates_multiple_attrs(tracer):
    st = _Stats()
    with obs.timed(st, "compute_s", "online_compute_s", span="work"):
        pass
    assert st.compute_s > 0
    assert st.compute_s == st.online_compute_s
    before = st.compute_s
    with obs.timed(st, "compute_s"):
        pass
    assert st.compute_s > before         # accumulates, not overwrites
    names = [e["name"] for e in tracer.drain()["events"]]
    assert names == ["work"]             # span=None records nothing


def test_timed_without_tracer_still_accumulates():
    prev = obs.install_tracer(obs.NULL_TRACER)
    try:
        st = _Stats()
        with obs.timed(st, "compute_s", span="work"):
            pass
        assert st.compute_s > 0
    finally:
        obs.install_tracer(prev)


def test_stopwatch():
    with obs.stopwatch() as sw:
        pass
    assert sw.s >= 0.0


def test_timed_records_thread_cpu(tracer):
    st = _Stats()
    st.compute_cpu_s = 0.0
    with obs.timed(st, "compute_s", cpu="compute_cpu_s", span="work"):
        sum(i * i for i in range(20000))
    assert 0.0 < st.compute_cpu_s <= st.compute_s
    (ev,) = tracer.drain()["events"]
    assert ev["args"]["cpu_s"] == st.compute_cpu_s


def test_deal_span_carries_thread_cpu(tracer):
    from repro.offline import deal
    _, rep = deal(_program, seed=3, meta={"session": 4})
    assert 0.0 < rep.cpu_s <= rep.wall_s
    (ev,) = [e for e in tracer.drain()["events"] if e["name"] == "deal"]
    assert ev["cat"] == "prep"
    assert ev["args"]["cpu_s"] == rep.cpu_s
    assert ev["args"]["session"] == 4
    assert ev["args"]["entries"] == rep.entries > 0


# ---------------------------------------------------------------------------
# The profiler sink: spans as TraceMe on the JAX profiler's host clock.
# ---------------------------------------------------------------------------
class _FakeTraceMe:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what the
    tracer builds."""

    built: list = []

    def __init__(self, name, **kwargs):
        self.name, self.meta = name, dict(kwargs)
        _FakeTraceMe.built.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kwargs):
        self.meta.update(kwargs)


@pytest.fixture
def fake_trace_me(monkeypatch):
    _FakeTraceMe.built = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeTraceMe)
    return _FakeTraceMe


def test_tracing_off_builds_no_trace_me(fake_trace_me):
    prev = obs.install_tracer(obs.NULL_TRACER)
    try:
        rt = FourPartyRuntime(seed=1)
        _program(rt)
        assert fake_trace_me.built == []
    finally:
        obs.install_tracer(prev)
    # the same program under the profiler sink does build them, so the
    # stand-in is the one the tracer would reach for
    prev = obs.install_tracer(obs.Tracer("test", sink="profiler"))
    try:
        _program(FourPartyRuntime(seed=1))
        assert any(t.name == "trident.protocol.matmul"
                   for t in fake_trace_me.built)
    finally:
        obs.install_tracer(prev)


def test_profiler_sink_prefixes_and_buffers_nothing(fake_trace_me):
    tr = obs.Tracer("test", sink="profiler")
    with tr.span("deal", "prep", seed=3, session=None) as sp:
        sp.set(cpu_s=0.25)
    tr.instant("send", cat="wire.send", bits=64)
    tr.counter("depth", 2)
    tr.raw_span("recv", "wire.recv", 0.0, 1.0)
    (tm,) = fake_trace_me.built
    assert tm.name == obs.PROFILER_PREFIX + "deal" == "trident.deal"
    assert tm.meta == {"seed": 3, "cpu_s": 0.25}    # None is left out
    assert tr.drain()["events"] == []
    with pytest.raises(ValueError, match="sink"):
        obs.Tracer("test", sink="file")


def _host_lines(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [[(e.name, dict(e.stats)) for e in line.events]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines]


def test_profiler_sink_lands_in_xplane_on_its_thread(tmp_path):
    from repro.offline import deal

    def dealt(rt):
        x = RT.share(rt, jnp.arange(4, dtype=jnp.int64))
        RT.mult(rt, x, x)

    deal(dealt, seed=1)                 # compile outside the profile
    prev = obs.install_tracer(obs.Tracer("test", sink="profiler"))
    try:
        def worker():
            with jax.profiler.TraceAnnotation("worker.marker"):
                deal(dealt, seed=2, meta={"session": 6})

        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("main.marker"):
                th = threading.Thread(target=worker)
                th.start()
                th.join()
    finally:
        obs.install_tracer(prev)
    lines = _host_lines(tmp_path)
    (line,) = [ln for ln in lines
               if any(n == "trident.protocol.mult" for n, _ in ln)]
    names = {n for n, _ in line}
    assert "worker.marker" in names and "main.marker" not in names
    assert "trident.deal" in names
    mult = next(st for n, st in line if n == "trident.protocol.mult")
    assert mult["session"] == 6 and mult["prep"] == "deal"
    deal_meta = next(st for n, st in line if n == "trident.deal")
    assert deal_meta["session"] == 6 and deal_meta["cpu_s"] > 0


# ---------------------------------------------------------------------------
# The pipelined serving stream: the wait for prep and the threads' CPU.
# ---------------------------------------------------------------------------
def _serve_predict(rt, Xb):
    from repro.core.ring import RING64
    xs = RT.share(rt, RING64.encode(Xb))
    w = RT.share(rt, RING64.encode(np.full((3, 2), 0.5)))
    return RING64.decode(RT.reconstruct(rt, RT.matmul_tr(rt, xs, w))[1])


def test_pipelined_server_counts_prep_wait_and_thread_cpu():
    from repro.serve.party_server import PartyPredictionServer

    reg = obs.get_registry()
    waited0 = reg.total("trident_prep_wait_us_total")
    srv = PartyPredictionServer(_serve_predict, batch_size=2, seed=5,
                                prep="pipelined")
    try:
        for q in np.random.RandomState(2).randn(4, 3):
            srv.submit(q)
        assert len(srv.flush()) == 4
    finally:
        srv.close()
    st = srv.stats
    assert st.batches == 2 and not st.aborted
    assert st.prep_wait_s > 0.0
    assert reg.total("trident_prep_wait_us_total") - waited0 == \
        pytest.approx(st.prep_wait_s * 1e6)
    assert 0.0 < st.offline_deal_cpu_s <= st.offline_deal_s
    assert 0.0 < st.online_compute_cpu_s <= st.online_compute_s


# ---------------------------------------------------------------------------
# The traced 4-process cluster (acceptance path, minus the dealer).
# ---------------------------------------------------------------------------
def _cluster_program(rt, rank):
    return np.asarray(_program(rt))


def test_cluster_trace_covers_all_ranks_and_matches_per_link():
    from repro.runtime.net.cluster import PartyCluster

    with PartyCluster(timeout=90.0, trace=True, metrics=True) as cluster:
        results = cluster.submit(_cluster_program, seed=11)
        assert cluster.trace
        chunks = cluster.trace_chunks
        assert len(chunks) == 4
        assert sorted(c["rank"] for c in chunks) == [0, 1, 2, 3]
        # the real wire's per-link bytes: every rank's registry snapshot
        # equals the full per-link accounting (replicated-program model)
        for r in results:
            chunk = r.trace
            assert chunk is not None and chunk["rank"] == r.rank
            want = {link: {p: b for p, b in per.items() if b}
                    for link, per in r.per_link.items()}
            assert obs.snapshot_link_bits(r.metrics) == \
                {link: per for link, per in want.items() if per}, r.rank
            assert r.prep_wait_s == 0.0  # no prep on this path
        doc = cluster.merged_trace()
        pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] != "M"}
        assert len(pids) == 4
        snap = obs.metrics_snapshot(doc)
        assert snap["rounds"]["online"]["count"] > 0
        assert len(cluster.task_walls) == 1 and cluster.task_walls[0] > 0


def test_cluster_untraced_ships_no_chunks():
    from repro.runtime.net.cluster import PartyCluster

    with PartyCluster(timeout=90.0) as cluster:
        results = cluster.submit(_cluster_program, seed=11)
        assert cluster.trace_chunks == []
        assert all(r.trace is None for r in results)


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-x", "-q"]))
