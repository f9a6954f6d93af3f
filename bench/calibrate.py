"""Measure the chip's 32-bit vector-operation peak, which no datasheet
gives.

    python bench/calibrate.py            # on the chip; prints one JSON line

One Pallas kernel keeps ``chains`` independent accumulators, each a full
``(8, 128)`` int32 vreg resident in VMEM, and runs ``a = max(a + y, z)``
on each of them ``iters`` times: two vector operations per element per
step, no memory traffic inside the loop, and no closed form a compiler
could fold.  The time of ``2 * iters`` steps minus that of ``iters``
steps cancels launch and copy overhead; the best of the chain counts and
of ``repeats`` tries is the peak.  The result is recorded in
``bench/work.py``.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

SUBLANES, LANES = 8, 128


def _kernel(x_ref, o_ref, *, iters: int, chains: int, unroll: int):
    ys = [x_ref[chains + k] for k in range(chains)]
    zs = [x_ref[2 * chains + k] for k in range(chains)]

    def body(_, acc):
        for _ in range(unroll):       # Mosaic unrolls only whole loops
            acc = tuple(jnp.maximum(a + y, z)
                        for a, y, z in zip(acc, ys, zs))
        return acc

    acc = jax.lax.fori_loop(0, iters // unroll, body,
                            tuple(x_ref[k] for k in range(chains)))
    for k in range(chains):
        o_ref[k] = acc[k]


def make(iters: int, chains: int, unroll: int = 8, interpret: bool = False):
    if iters % unroll:
        raise ValueError(f"iters {iters} is not a multiple of unroll {unroll}")
    kern = functools.partial(_kernel, iters=iters, chains=chains,
                             unroll=unroll)
    return jax.jit(pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((chains, SUBLANES, LANES),
                                             jnp.int32),
        interpret=interpret))


def inputs(chains: int, seed: int = 0) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    x = rng.integers(-1000, 1000, size=(3 * chains, SUBLANES, LANES))
    return jnp.asarray(x.astype(np.int32))


def expected(x: np.ndarray, iters: int, chains: int) -> np.ndarray:
    a, y, z = x[:chains], x[chains:2 * chains], x[2 * chains:]
    for _ in range(iters):
        a = np.maximum(a + y, z)
    return a


def _time(fn, x, repeats: int) -> float:
    jax.block_until_ready(fn(x))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        best = min(best, time.perf_counter() - t0)
    return best


def measure(iters: int = 1 << 22, chain_counts=(4, 8, 16),
            repeats: int = 5) -> dict:
    rows = []
    for chains in chain_counts:
        x = inputs(chains)
        t1 = _time(make(iters, chains), x, repeats)
        t2 = _time(make(2 * iters, chains), x, repeats)
        ops = 2 * iters * chains * SUBLANES * LANES
        rows.append({"chains": chains, "t_iters_s": t1, "t_2iters_s": t2,
                     "ops_per_s": ops / (t2 - t1)})
    best = max(rows, key=lambda r: r["ops_per_s"])
    d = jax.devices()[0]
    return {"device_kind": d.device_kind, "platform": d.platform,
            "int32_ops_per_s": best["ops_per_s"], "runs": rows}


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 1
    print(json.dumps(measure()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
