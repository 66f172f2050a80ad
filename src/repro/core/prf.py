"""Shared-key setup (F_setup, paper Fig. 21) and counter-mode PRF sampling.

The paper establishes PRF keys between every pair / triple of parties and one
global key; all lambda-masks and zero-shares are then sampled
*non-interactively* from these keys.  Key management stays on JAX's threefry
(a key per party-subset; every protocol invocation folds in a fresh
*statically allocated* counter, so traced programs are pure functions of
(inputs, base key, static counters) -- which is what makes deterministic
replay (fault tolerance) and offline/online twin-tracing work).

The ring-element stream itself is the `squares` counter RNG (Widynski 2020)
keyed per invocation: ``squares_key`` derives a 64-bit key from
(subset key, counter) and ``squares_stream`` expands it counter-mode into
uniform ring elements.  This is the SAME function the fused Pallas kernel
``kernels/prf_mask.py`` computes (asserted bit-exact in tests), which is
what lets the runtime's pallas kernel backend generate -- and the prep seam
REgenerate -- lambda masks in-kernel while staying bit-identical to the
joint simulation and the jnp backend.  A jnp draw (``prf_bits`` /
``prf_bounded``) is one compiled program per (shape, ring, shift), the
key and the counter its operands.  It stands in for the paper's
fixed-key AES-CTR F_k; pseudorandomness is the only property the protocols
use (docs/KERNELS.md).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.registry import get_registry
from .ring import Ring

PARTIES = (0, 1, 2, 3)


def subset_id(subset: Iterable[int]) -> int:
    """Encode a party subset as a bitmask (e.g. {0,1} -> 0b0011)."""
    m = 0
    for p in subset:
        m |= 1 << p
    return m


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SetupKeys:
    """F_setup output: one master key; subset keys derived by fold_in.

    In a real deployment each party only holds the subset keys it belongs to;
    the joint simulation holds the master and derives per-subset streams with
    identical semantics (a party outside subset S cannot predict S's stream).
    """

    master: jax.Array  # jax PRNG key

    def subset_key(self, subset: Iterable[int]) -> jax.Array:
        return jax.random.fold_in(self.master, subset_id(subset))

    def tree_flatten(self):
        return (self.master,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])


def make_setup_keys(seed: int = 0) -> SetupKeys:
    return SetupKeys(jax.random.key(seed))


_GOLDEN = 0x9E3779B97F4A7C15


def squares_key(key: jax.Array, counter: int) -> jax.Array:
    """Derive the per-invocation 64-bit `squares` key from a threefry subset
    key and the statically-allocated protocol counter.  Returns a (1,)
    uint64 -- exactly the key operand ``kernels.ops.lambda_masks`` takes, so
    a recorded (subset, counter) pair is enough to regenerate any lambda
    stream at the point of use (the keyed-lambda representation)."""
    data = jax.random.key_data(jax.random.fold_in(key, counter))
    kd = jnp.asarray(data, jnp.uint64).ravel()
    k64 = ((kd[0] << jnp.uint64(32)) | kd[1]) ^ jnp.uint64(_GOLDEN)
    # force an odd key: guarantees full-period counter mixing for `squares`
    return (k64 | jnp.uint64(1)).reshape((1,))


def squares_stream(key64: jax.Array, n: int, counter0: int = 0) -> jax.Array:
    """Counter-mode `squares` PRF: (n,) uniform uint64 from a (1,) uint64
    key.  The pure-jnp twin of the Pallas kernel ``kernels/prf_mask.py``
    (same 4 mul/add/rotate rounds, bit-exact -- tests/test_kernel_backend.py
    asserts the parity that underwrites cross-backend bit-identity)."""
    key = jnp.asarray(key64, jnp.uint64).reshape(())
    ctr = jnp.arange(counter0, counter0 + n, dtype=jnp.uint64)
    x = ctr * key
    y = x
    z = y + key

    def rot32(v):
        return (v >> jnp.uint64(32)) | (v << jnp.uint64(32))

    x = rot32(x * x + y)
    x = rot32(x * x + z)
    x = rot32(x * x + y)
    x = x * x + z
    t = x
    x = rot32(x)
    return t ^ ((x * x + y) >> jnp.uint64(32))


def _numel(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


@functools.partial(jax.jit, static_argnames=("shape", "ring", "shift"))
def _draw(key, counter, *, shape, ring, shift):
    """One PRF draw as one XLA program per (shape, ring, shift); the key
    and the counter are operands, so every draw of a shape reuses it."""
    if isinstance(counter, jax.core.Tracer):      # tracing, not disable_jit
        get_registry().counter(
            "trident_prf_compiles_total",
            "PRF draw programs traced: one per (shape, ring, shift)").inc()
    out = squares_stream(squares_key(key, counter), _numel(shape))
    out = out.reshape(shape).astype(ring.dtype)
    return out >> shift if shift else out


def prf_bits(key: jax.Array, counter: int, shape, ring: Ring) -> jax.Array:
    """F_k(counter) -> uniform ring elements of `shape` (counter-mode PRF)."""
    return _draw(key, np.uint32(counter), shape=tuple(map(int, shape)),
                 ring=ring, shift=0)


def prf_bounded(key: jax.Array, counter: int, shape, ring: Ring,
                bits: int) -> jax.Array:
    """Uniform over [0, 2^bits) embedded in the ring (used by guarded BitExt)."""
    return _draw(key, np.uint32(counter), shape=tuple(map(int, shape)),
                 ring=ring, shift=ring.ell - bits)
