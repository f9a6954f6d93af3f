"""Compile-and-time search over the pruned design space.

For one tuning point — (kernel, engine, bucket, batch) on the current
backend — the sweep:

1. enumerates the legal space (``space.enumerate_space``),
2. prunes to the top-K predicted candidates (``cost.rank``; the
   hand-picked default always survives),
3. compiles each survivor through the real plan cache (``get_plan`` with
   *explicit* options, so the sweep never consults the very table it is
   writing) and times it — warmup dispatch first, then median of N,
4. asserts every candidate's output against the default plan's before
   its timing counts: bit-identical for max/min semirings (schedule
   knobs are result-preserving by construction — any mismatch is a bug,
   not noise), small-tolerance for logsumexp (strip reshapes the
   float-add reduction order),
5. picks the measured-fastest candidate.  The default is always among
   the measured set, so the winner matches-or-beats the hand-picked
   schedule on the very run that recorded it.

Timing draws request lengths from ``(bucket/2, bucket]`` — the
distribution power-of-two bucketing guarantees — so early-exit savings
are measured at serving-realistic, not best-case, lengths.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import numpy as np

from repro.runtime import plan as plan_mod

from . import cost as cost_mod
from . import space as space_mod
from .table import TuningTable

# logsumexp reductions reassociate across strip widths; scores are
# float32 log-space sums over <= a few thousand terms
LSE_RTOL, LSE_ATOL = 1e-5, 1e-5


def make_batch(rng, spec, bucket: tuple, batch_size: Optional[int]):
    """Random padded inputs matching the kernel's alphabet, lengths in
    the ``(bucket/2, bucket]`` range bucketing guarantees."""
    import jax.numpy as jnp
    n = batch_size or 1
    nq, nr = bucket

    def seqs(length):
        if spec.char_shape == (5,):
            from repro.core.kernels_zoo.profile import make_profile
            return np.stack([make_profile(rng, length) for _ in range(n)])
        if spec.char_shape == (2,):
            return rng.normal(size=(n, length, 2)).astype(np.float32)
        if jnp.dtype(spec.char_dtype) == jnp.int32:
            return rng.integers(0, 128, (n, length)).astype(np.int32)
        hi = 20 if spec.name == "protein_local" else 4
        return rng.integers(0, hi, (n, length)).astype(np.uint8)

    qs, rs = seqs(nq), seqs(nr)
    ql = rng.integers(nq // 2 + 1, nq + 1, n).astype(np.int32)
    rl = rng.integers(nr // 2 + 1, nr + 1, n).astype(np.int32)
    if batch_size is None:
        return (jnp.asarray(qs[0]), jnp.asarray(rs[0]),
                jnp.asarray(ql[0]), jnp.asarray(rl[0]))
    return (jnp.asarray(qs), jnp.asarray(rs),
            jnp.asarray(ql), jnp.asarray(rl))


def assert_parity(spec, ref_out, out, ctx: str = "") -> None:
    """Candidate output must equal the default plan's.

    Max/min semirings: bit-identical on every leaf.  Logsumexp: float
    leaves compare within (LSE_RTOL, LSE_ATOL); integer leaves exact.
    """
    a_leaves = jax.tree_util.tree_leaves(ref_out)
    b_leaves = jax.tree_util.tree_leaves(out)
    assert len(a_leaves) == len(b_leaves), \
        f"{ctx}: output structure mismatch"
    lse = spec.semiring.name == "logsumexp"
    for i, (a, b) in enumerate(zip(a_leaves, b_leaves)):
        a, b = np.asarray(a), np.asarray(b)
        if lse and np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(
                a, b, rtol=LSE_RTOL, atol=LSE_ATOL,
                err_msg=f"{ctx}: leaf {i}")
        else:
            np.testing.assert_array_equal(a, b,
                                          err_msg=f"{ctx}: leaf {i}")


def _time_plan(plan, params, data, *, iters: int) -> float:
    """Median wall seconds per dispatch (first call warms/compiles)."""
    jax.block_until_ready(plan(params, *data))
    ts = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(plan(params, *data))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def tune_point(spec, params, engine_name: str, bucket: tuple,
               batch_size: Optional[int] = None, *,
               with_traceback: bool = True, mode: str = "align",
               top_k: int = 4, iters: int = 3, seed: int = 0,
               log=None) -> Optional[dict]:
    """Search one point; returns the winner record (or ``None`` for an
    engine with nothing to tune)."""
    candidates = space_mod.enumerate_space(spec, engine_name)
    if not candidates:
        return None
    default = space_mod.default_options(spec, engine_name)
    wtb = bool(with_traceback and spec.traceback is not None)
    kept, pruned = cost_mod.rank(
        spec, params, engine_name, bucket, batch_size, candidates,
        default=default, top_k=top_k, with_traceback=wtb, mode=mode,
        log=log)

    rng = np.random.default_rng(seed)
    data = make_batch(rng, spec, bucket, batch_size)
    char = spec.char_shape
    q_shape, r_shape = (bucket[0],) + char, (bucket[1],) + char
    if batch_size is None:
        cells = float(data[2]) * float(data[3])
    else:
        cells = float((np.asarray(data[2], np.int64)
                       * np.asarray(data[3], np.int64)).sum())

    def plan_for(opts):
        return plan_mod.get_plan(
            spec, engine_name, q_shape, r_shape, batch_size=batch_size,
            with_traceback=wtb, mode=mode, **opts)

    ref_out = plan_for(default)(params, *data)
    jax.block_until_ready(ref_out)

    measurements = []
    for s in kept:
        opts = s["options"]
        plan = plan_for(opts)
        out = plan(params, *data)
        assert_parity(spec, ref_out, out,
                      ctx=f"{spec.name}/{engine_name}/{bucket}/"
                          f"{batch_size}/{opts}")
        secs = _time_plan(plan, params, data, iters=iters)
        measurements.append({**s, "seconds": secs,
                             "cells_per_s": cells / secs})
        if log is not None:
            log(f"measured {opts}: {cells / secs:.3g} cells/s")
    best = max(measurements, key=lambda m: m["cells_per_s"])
    base = next(m for m in measurements if m["options"] == default)
    return {"options": best["options"],
            "cells_per_s": best["cells_per_s"],
            "default_options": default,
            "default_cells_per_s": base["cells_per_s"],
            "speedup_vs_default": best["cells_per_s"]
            / base["cells_per_s"],
            "measurements": measurements,
            "n_pruned": len(pruned)}


def run_sweep(points, *, table: Optional[TuningTable] = None,
              top_k: int = 4, iters: int = 3, seed: int = 0,
              log=None, clear_between: bool = True) -> TuningTable:
    """Tune every ``(kernel, engine, bucket, batch_size)`` point and
    record the winners into a :class:`TuningTable`.

    ``clear_between`` retires each point's compiled executables
    (``clear_plan_cache(keep_stats=True)``) so a long sweep's memory
    stays bounded while ``plan_cache_info()['totals']`` keeps the full
    compile-time accounting.
    """
    from repro.core import kernels_zoo

    table = table if table is not None else TuningTable()
    for kernel, engine_name, bucket, batch_size in points:
        spec, params = kernels_zoo.make(kernel)
        res = tune_point(spec, params, engine_name, tuple(bucket),
                         batch_size, top_k=top_k, iters=iters, seed=seed,
                         log=log)
        if res is None:
            if log is not None:
                log(f"skip {kernel}/{engine_name}: nothing to tune")
            continue
        key = table.record(
            kernel, engine_name, tuple(bucket), batch_size,
            res["options"],
            cells_per_s=res["cells_per_s"],
            default_options=res["default_options"],
            default_cells_per_s=res["default_cells_per_s"],
            speedup_vs_default=res["speedup_vs_default"])
        if log is not None:
            log(f"{key} -> {res['options']} "
                f"({res['speedup_vs_default']:.2f}x vs default)")
        if clear_between:
            plan_mod.clear_plan_cache(keep_stats=True)
    return table
