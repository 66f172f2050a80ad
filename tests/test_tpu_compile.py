"""The main path's device programs compile for a TPU v5e chip.

XLA:TPU has no u64 dot, and Mosaic (the TPU kernel compiler) no 64-bit
types, so a program that runs on the CPU can still be refused by the
chip's compiler.  These tests compile, for one chip of a described
``v5e:2x2`` topology (no chip attached), the programs the paper's NN
784-128-128-10 runs at batch 128: the ring matmul at its forward and
backward shapes, the squares PRF, and every Pallas kernel the "pallas"
kernel backend launches, compiled by Mosaic (not interpreted).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.  The persistent compilation
cache is off around the compiles (an entry compiled for a described chip
cannot be read back without one).
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.core import prf
from repro.core.ring import RING32, RING64
from repro.kernels import limb_matmul as LM
from repro.kernels import mpc_matmul_fused as MF
from repro.kernels import ops

BATCH, FEATURES, HIDDEN = 128, 784, 128

# (lhs, rhs) of every matmul shape one NN training step contracts
NN_MATMULS = {
    "fwd_x_w0": ((BATCH, FEATURES), (FEATURES, HIDDEN)),
    "bwd_dw0": ((FEATURES, BATCH), (BATCH, HIDDEN)),
    "bwd_dx": ((BATCH, HIDDEN), (HIDDEN, FEATURES)),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_for(one_chip, fn, args):
    """Compile fn for the described chip on ``args(S)``, S(*shape,
    dtype=u64) making an operand; returns the compiled HLO text."""
    def S(*shape, dtype=jnp.uint64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return jax.jit(fn).lower(*args(S)).compile().as_text()


@pytest.mark.parametrize("shapes", list(NN_MATMULS.values()),
                         ids=list(NN_MATMULS))
@pytest.mark.parametrize("ring", [RING32, RING64], ids=["ell32", "ell64"])
def test_ring_matmul_compiles(one_chip, no_cache, ring, shapes):
    sa, sb = shapes
    compile_for(one_chip, ring.matmul, lambda S: (S(*sa, dtype=ring.dtype),
                                                  S(*sb, dtype=ring.dtype)))


def test_u64_dot_is_what_the_chip_refuses(one_chip, no_cache):
    """The reason Ring.matmul is built from int8 dots."""
    sa, sb = NN_MATMULS["fwd_x_w0"]
    with pytest.raises(Exception, match="X64"):
        compile_for(one_chip, jnp.matmul, lambda S: (S(*sa), S(*sb)))


def test_squares_prf_compiles(one_chip, no_cache):
    compile_for(one_chip, lambda k: prf.squares_stream(k, FEATURES * HIDDEN),
                lambda S: (S(1),))


@pytest.mark.parametrize("shape,shift", [((FEATURES, HIDDEN), 0),
                                         ((BATCH, HIDDEN), 4)])
def test_prf_draw_compiles(one_chip, no_cache, shape, shift):
    """One PRF draw as the dealer runs it: subset key and counter in."""
    def draw(key, counter):
        return prf._draw(key, counter, shape=shape, ring=RING64,
                         shift=shift)
    key = jax.random.key(0)
    compile_for(one_chip, draw, lambda S: (S(*key.shape, dtype=key.dtype),
                                           S(dtype=jnp.uint32)))


# every kernel the "pallas" backend launches, at the NN's widths, compiled
# by Mosaic: name -> (fn, operands)
N = BATCH * HIDDEN
PALLAS_KERNELS = {
    "ring_matmul": (functools.partial(LM.ring_matmul, interpret=False),
                    lambda S: (S(BATCH, FEATURES), S(FEATURES, HIDDEN))),
    "mpc_matmul_grid": (
        lambda xs, ys: MF.mpc_matmul_grid(xs, ys, interpret=False),
        lambda S: ((S(BATCH, FEATURES),) * 3, (S(FEATURES, HIDDEN),) * 3)),
    "mult_terms": (
        lambda a, b, c: ops._grouped_mult(a, b, c, (1, 1, 1), False),
        lambda S: (S(3, 3, N), S(3, 3, N), S(3, N))),
    "and_terms": (lambda a, b, c: ops._grouped_and(a, b, c, False),
                  lambda S: (S(3, 3, N), S(3, 3, N), S(3, N))),
    "lambda_masks": (lambda k: ops._masks(k, FEATURES * HIDDEN, 0, False),
                     lambda S: (S(1),)),
}


@pytest.mark.parametrize("name", list(PALLAS_KERNELS))
def test_pallas_kernel_compiles(one_chip, no_cache, name):
    fn, args = PALLAS_KERNELS[name]
    hlo = compile_for(one_chip, fn, args)
    assert "tpu_custom_call" in hlo        # a Mosaic kernel, not interpreted
