"""Pallas TPU kernels for the protocol hot spots (docs/KERNELS.md has the
per-kernel contracts):

  limb_matmul       ring matmul (Z_2^32/64) on the MXU via 4-bit limbs
  mpc_matmul_fused  all online-phase products of Pi_MatMulTr in one pass
                    (plus the general all-pairs ``mpc_matmul_grid``)
  gamma_parts       grouped fused-FMA / XOR-AND term kernels backing the
                    runtime's pallas kernel backend
  ppa_msb           fused local math of a boolean PPA/AND level
  prf_mask          counter-mode lambda-mask generation (keyed-lambda)

ops.py holds the jit'd wrappers: the u64 <-> u32-word boundary (words.py;
Mosaic has no 64-bit types) and the platform's choice of interpreting
(CPU) or compiling (TPU) every kernel; ref.py the pure-jnp oracles
every kernel is asserted against (tests/test_kernels.py).  The party
runtime routes its local compute through these via
repro.runtime.kernel_backend (TRIDENT_RUNTIME_KERNELS=1).
"""
