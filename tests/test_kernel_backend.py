"""The kernel-backend seam contract (runtime/kernel_backend.py).

The runtime's local compute is pluggable -- "jnp" (per-component shared
algebra) or "pallas" (fused kernels) -- and the two backends must be
BIT-IDENTICAL at every level:

  * the raw PRF streams (core.prf.squares_stream == the prf_mask kernel
    == the oracle), which is what lets the prep seam regenerate dealt
    lambda masks from (subset key, counter) alone;
  * per-protocol outputs AND measured wire traffic (the transport totals
    never depend on the backend -- local compute moves no bytes);
  * the boolean world (AND / PPA), activations, and a full secure-SGD
    training step;
  * the offline/online split, including MIXED backends: material dealt
    by a jnp dealer consumed by a pallas online run, and vice versa.
"""
import numpy as np
import pytest

from repro.core import algebra as AL
from repro.core import prf
from repro.core.ring import RING32, RING64
from repro.kernels import ops
from repro.kernels import ref as R
from repro.offline import deal, run_online
from repro.runtime import FourPartyRuntime
from repro.runtime import activations as RA
from repro.runtime import boolean as RB
from repro.runtime import protocols as RT
from repro.runtime.kernel_backend import (JnpKernels, PallasKernels,
                                          make_kernel_backend)

import jax
import jax.numpy as jnp


def enc(x):
    return RING64.encode(np.asarray(x))


# ---------------------------------------------------------------------------
# PRF parity: jnp twin == Pallas kernel == oracle.
# ---------------------------------------------------------------------------
class TestPrfParity:
    @pytest.mark.parametrize("n", [7, 512, 1000])
    def test_squares_stream_matches_kernel_and_ref(self, n):
        key64 = jnp.asarray([0x9E3779B97F4A7C15 | 1], jnp.uint64)
        twin = prf.squares_stream(key64, n)
        kern = ops.lambda_masks(key64, n)     # pads to 512 and slices
        klo = (key64 & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)[0]
        khi = (key64 >> jnp.uint64(32)).astype(jnp.uint32)[0]
        oracle = R.prf_mask_ref(klo, khi, 0, (n,))
        assert np.array_equal(np.asarray(twin), np.asarray(kern))
        assert np.array_equal(np.asarray(twin), np.asarray(oracle))

    @pytest.mark.parametrize("ring", [RING64, RING32])
    @pytest.mark.parametrize("shape", [(3,), (5, 7), (512,)])
    def test_prf_bits_backends_identical(self, ring, shape):
        key = jax.random.key(42)
        a = JnpKernels().prf_bits(key, 9, shape, ring)
        b = PallasKernels().prf_bits(key, 9, shape, ring)
        assert a.dtype == b.dtype == ring.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("ring", [RING64, RING32])
    def test_prf_bounded_backends_identical(self, ring):
        key = jax.random.key(7)
        a = JnpKernels().prf_bounded(key, 3, (11,), ring, 20)
        b = PallasKernels().prf_bounded(key, 3, (11,), ring, 20)
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert int(np.asarray(a).max()) < 2 ** 20


# ---------------------------------------------------------------------------
# The compiled PRF draw: one program per (shape, ring, shift), bit-identical
# to the eager composition, the counter an operand.
# ---------------------------------------------------------------------------
def _eager_draw(key, counter, shape, ring):
    n = int(np.prod(shape))
    out = prf.squares_stream(prf.squares_key(key, counter), n)
    return out.reshape(shape).astype(ring.dtype)


class TestCompiledDraw:
    @pytest.mark.parametrize("bits", [None, 60, 39])
    @pytest.mark.parametrize("counter", [0, 322, 2 ** 31 + 5])
    @pytest.mark.parametrize("shape", [(11,), (128, 1), (36, 128, 1),
                                       (128, 784)])
    @pytest.mark.parametrize("ring", [RING64, RING32])
    def test_draw_equals_eager_composition(self, ring, shape, counter,
                                           bits):
        """bits None is prf_bits; otherwise prf_bounded, whose shift
        (ell - bits) the 32-bit ring keeps at the 64-bit ring's value."""
        key = jax.random.key(5)
        want = _eager_draw(key, counter, shape, ring)
        if bits is None:
            got = prf.prf_bits(key, counter, shape, ring)
        else:
            bits -= 64 - ring.ell
            got = prf.prf_bounded(key, counter, shape, ring, bits)
            want = want >> (ring.ell - bits)
        assert got.dtype == want.dtype and got.shape == tuple(shape)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_redeal_compiles_nothing_and_matches_eager_deal(self):
        """Once a deal has traced the program's draws, a second deal that
        runs it twice traces none anew -- its second pass draws at counters
        the first deal never used, which a static counter would trace --
        and a deal's material equals the same seed dealt with every jit
        disabled."""
        from repro.nn.runtime_engine import RuntimeEngine
        from repro.obs import get_registry
        from repro.train import paper_ml as PML

        net = PML.MLPNet(features=8, layers=(4, 2))
        rng = np.random.RandomState(3)
        params = PML.mlp_net_init(rng, net)
        X = rng.randn(4, 8)

        def program(rt):
            eng = RuntimeEngine(rt)
            sh = {k: eng.from_plain(params[k]) for k in sorted(params)}
            return PML.mlp_net_predict(eng, sh, net, eng.from_plain(X))

        def twice(rt):
            return program(rt), program(rt)

        reg = get_registry()
        store, _ = deal(program, seed=21)
        compiles = reg.total("trident_prf_compiles_total")
        launches = reg.total("trident_kernel_launches_total")
        deal(twice, seed=21)
        assert reg.total("trident_kernel_launches_total") > launches
        assert reg.total("trident_prf_compiles_total") == compiles
        with jax.disable_jit():
            eager, _ = deal(program, seed=21)
        assert store.tags() == eager.tags() and len(store) > 0
        for tag in store.tags():
            kind, parts = store._entries[tag]
            ekind, eparts = eager._entries[tag]
            assert kind == ekind
            flat = jax.tree_util.tree_leaves_with_path(parts)
            eflat = jax.tree_util.tree_leaves_with_path(eparts)
            assert [p for p, _ in flat] == [p for p, _ in eflat], tag
            for (_, a), (_, b) in zip(flat, eflat):
                assert a.dtype == b.dtype and np.array_equal(a, b), tag


# ---------------------------------------------------------------------------
# Backend resolution (the TRIDENT_RUNTIME_KERNELS env seam).
# ---------------------------------------------------------------------------
class TestBackendResolution:
    def test_default_is_jnp(self, monkeypatch):
        monkeypatch.delenv("TRIDENT_RUNTIME_KERNELS", raising=False)
        assert make_kernel_backend(None).name == "jnp"
        assert FourPartyRuntime(RING64).kernels.name == "jnp"

    def test_env_flag_selects_pallas(self, monkeypatch):
        monkeypatch.setenv("TRIDENT_RUNTIME_KERNELS", "1")
        assert make_kernel_backend(None).name == "pallas"
        assert FourPartyRuntime(RING64).kernels.name == "pallas"

    def test_explicit_string_overrides_env(self, monkeypatch):
        monkeypatch.setenv("TRIDENT_RUNTIME_KERNELS", "1")
        assert make_kernel_backend("jnp").name == "jnp"

    def test_instance_passthrough_and_unknown_name(self):
        be = PallasKernels()
        assert make_kernel_backend(be) is be
        with pytest.raises(ValueError, match="unknown kernel backend"):
            make_kernel_backend("cuda")


# ---------------------------------------------------------------------------
# Protocol-level identity: outputs AND wire totals match across backends.
# ---------------------------------------------------------------------------
VALS_X = np.linspace(-2.0, 2.0, 5)
VALS_Y = np.linspace(0.5, 1.5, 5)
BITS_X = np.asarray([5, 2 ** 63 + 11, 123456789], np.uint64)
BITS_Y = np.asarray([9, 2 ** 62 + 3, 987654321], np.uint64)


def _mult(rt):
    xs, ys = RT.share(rt, enc(VALS_X)), RT.share(rt, enc(VALS_Y))
    return RT.mult_tr(rt, xs, ys)


def _dotp(rt):
    xs, ys = RT.share(rt, enc(VALS_X)), RT.share(rt, enc(VALS_Y))
    return RT.dotp(rt, xs, ys)


def _matmul(rt):
    rng = np.random.RandomState(3)
    a = RT.share(rt, enc(rng.randn(4, 8)))
    b = RT.share(rt, enc(rng.randn(8, 5) * 0.3))
    return RT.matmul_tr(rt, a, b)


def _ppa(rt):
    x = RT.share_bool(rt, BITS_X)
    y = RT.share_bool(rt, BITS_Y)
    return RB.ppa_add(rt, x, y)


def _relu(rt):
    return RA.relu(rt, RT.share(rt, enc(VALS_X)))


def _sigmoid(rt):
    return RA.sigmoid(rt, RT.share(rt, enc(VALS_X)))


PROGRAMS = {"mult_tr": _mult, "dotp": _dotp, "matmul_tr": _matmul,
            "ppa_add": _ppa, "relu": _relu, "sigmoid": _sigmoid}


def _run(program, backend, seed=11):
    rt = FourPartyRuntime(RING64, seed=seed, kernel_backend=backend)
    out = program(rt)
    assert not bool(rt.abort_flag())
    return (np.asarray(out.to_joint().data), rt.transport.totals())


class TestBackendIdentity:
    @pytest.mark.parametrize("op", sorted(PROGRAMS))
    def test_outputs_and_wire_identical(self, op):
        jout, jtot = _run(PROGRAMS[op], "jnp")
        pout, ptot = _run(PROGRAMS[op], "pallas")
        assert np.array_equal(jout, pout), f"{op}: backend outputs diverge"
        # local compute moves no bytes: wire == CostTally in both modes
        assert jtot == ptot, f"{op}: backend wire totals diverge"

    def test_train_step_identical(self):
        from repro.train import data as D
        from repro.train import secure_sgd as SGD
        task = SGD.logreg_task(features=6, lr=0.5)
        params = task.init_params(seed=0)
        batch = D.RegressionData(features=6, n=64, seed=1,
                                 logistic=True).batch(0, 4)
        outs = {}
        for backend in ("jnp", "pallas"):
            rt = FourPartyRuntime(RING64, seed=5, kernel_backend=backend)
            new, loss, _ = SGD.step_program(task, params, batch)(rt)
            assert not bool(rt.abort_flag())
            outs[backend] = ({k: np.asarray(new[k]) for k in new}, loss,
                             rt.transport.totals())
        jp, pl = outs["jnp"], outs["pallas"]
        assert jp[1] == pl[1] and jp[2] == pl[2]
        for k in jp[0]:
            assert np.array_equal(jp[0][k], pl[0][k]), k


# ---------------------------------------------------------------------------
# Prep seam: dealt lambda masks regenerate from (subset key, counter)
# through the kernel PRF -- the keyed-lambda representation.
# ---------------------------------------------------------------------------
class TestPrepSeamRegeneration:
    def test_share_lambdas_regenerate_via_kernel_prf(self):
        rt = FourPartyRuntime(RING64, seed=3)
        c0 = rt._counter
        v = enc(np.linspace(-1.0, 1.0, 9).reshape(3, 3))
        xs = RT.share(rt, v)
        # share() samples lam_j at counters c0, c0+1, c0+2 (program order)
        for k, j in enumerate((1, 2, 3)):
            subset = AL.lam_holders(j)
            key = rt.parties[min(subset)].keys.subset_key(subset)
            regen = ops.lambda_masks(prf.squares_key(key, c0 + k),
                                     v.size).reshape(v.shape)
            holder = subset[0] if subset[0] != 0 else subset[1]
            assert np.array_equal(np.asarray(regen),
                                  np.asarray(xs.views[holder].lam[j])), j

    @pytest.mark.parametrize("deal_be,online_be",
                             [("jnp", "pallas"), ("pallas", "jnp")])
    def test_deal_and_online_backends_mix(self, deal_be, online_be):
        def program(rt):
            xs = RT.share(rt, enc(VALS_X))
            z = RA.relu(rt, RT.mult_tr(rt, xs, xs))
            return np.asarray(RT.reconstruct(rt, z)[1])

        ref = program(FourPartyRuntime(RING64, seed=17))
        store, _ = deal(program, ring=RING64, seed=17,
                        runtime_kwargs={"kernel_backend": deal_be})
        out, rep = run_online(program, store, ring=RING64,
                              runtime_kwargs={"kernel_backend": online_be})
        assert rep.offline_bits == 0
        assert np.array_equal(np.asarray(out), ref)
