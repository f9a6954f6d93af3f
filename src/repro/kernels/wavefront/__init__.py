"""Pallas TPU wavefront matrix-fill kernel (kernel.py), its jit wrapper
(ops.py) and pure-jnp oracle (ref.py).

``N_PE`` — the strip height, one PE per VPU lane — and the most scoped
VMEM the kernel asks the compiler for are defined here, away from the
Pallas imports, so that the plan layer and the linter can size the
kernel without loading Pallas.
"""

N_PE = 128
# a TPU v5e core has 128 MiB of VMEM; leave the compiler headroom
VMEM_CAP_BYTES = 100 << 20
