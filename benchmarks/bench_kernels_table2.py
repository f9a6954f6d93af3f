"""Paper Table 2 analogue: all 15 kernels on the shared wavefront back-end.

Columns: alignments/s and GCUPS (DP cells/s) measured on XLA:CPU for a
batch of sequence pairs, plus the VMEM working-set the Pallas kernel would
claim on TPU for the same spec (the resource-utilization analogue).
"""
from __future__ import annotations

import numpy as np

from repro.core import kernels_zoo
from repro.kernels.wavefront import ops as wops
from .common import batched_plan, emit, kernel_batch, timeit

N, NQ, NR = 16, 128, 128


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    n = 8 if quick else N
    for kid in range(1, 16):
        name, _, _ = kernels_zoo.KERNELS[kid]
        spec, params = kernels_zoo.make(kid)
        qs, rs, ql, rl = kernel_batch(rng, spec, n, NQ, NR)
        fn = batched_plan(spec, n, NQ, NR)
        sec = timeit(fn, params, qs, rs, ql, rl)
        aps = n / sec
        gcups = n * NQ * NR / sec / 1e9
        emit(f"table2/{kid:02d}_{name}", sec / n,
             f"aligns_per_s={aps:.0f} gcups={gcups:.3f} "
             f"vmem_kib={wops.vmem_bytes(spec, 4096, 4096, params) / 1024:.0f} "
             f"n_layers={spec.n_layers}")


if __name__ == "__main__":
    run()
