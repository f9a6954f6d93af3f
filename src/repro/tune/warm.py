"""AOT plan warming: pay trace + XLA compile at boot, not on request 1.

A cold service's first request at each (kernel, bucket, batch) channel
stalls for the full trace+compile of that channel's plan — seconds on
the big buckets, against a sub-millisecond dispatch once hot.  Warming
walks a service's channel grid at construction (``warm_start=``) and
forces each plan through its first dispatch with a dummy length-1 batch:
compilation is triggered (JAX compiles for the padded *shape*; lengths
are runtime values, so a length-1 fill is the cheapest dispatch that
fully builds the executable), and the real first request then hits a hot
cache entry.

Cold-vs-warm is measurable, not anecdotal: every ``CompiledPlan`` stamps
its first-dispatch ``compile_s``, and ``plan_cache_info()['totals']
['compile_s']`` sums it across live + retired plans — time-to-first-result
moved from request latency to boot.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import plan as plan_mod


def _dummy_args(spec, q_shape: tuple, r_shape: tuple,
                batch_size: Optional[int]):
    """Zero-filled inputs at the bucket shape, lengths pinned to 1 (the
    cheapest fill the early-exit engines can run)."""
    dtype = np.dtype(jnp.dtype(spec.char_dtype).name)
    if batch_size is None:
        q = np.zeros(q_shape, dtype)
        r = np.zeros(r_shape, dtype)
        ql = rl = np.int32(1)
    else:
        q = np.zeros((batch_size,) + tuple(q_shape), dtype)
        r = np.zeros((batch_size,) + tuple(r_shape), dtype)
        ql = np.ones((batch_size,), np.int32)
        rl = np.ones((batch_size,), np.int32)
    return (jnp.asarray(q), jnp.asarray(r), jnp.asarray(ql),
            jnp.asarray(rl))


def warm_plan(spec, params, engine_name: str, q_shape: tuple,
              r_shape: tuple, *, batch_size: Optional[int] = None,
              with_traceback: bool = True, mode: str = "align",
              **options) -> plan_mod.CompiledPlan:
    """Fetch the plan ``get_plan`` would serve for these arguments and
    force its compile with one dummy dispatch (no-op if already hot).

    Passing no explicit ``options`` means the warmed plan goes through
    the same tuned-table default resolution a live request would — the
    warmed executable IS the served executable.
    """
    plan = plan_mod.get_plan(
        spec, engine_name, tuple(q_shape), tuple(r_shape),
        batch_size=batch_size, with_traceback=with_traceback, mode=mode,
        **options)
    if plan.compile_s is None:
        out = plan(params, *_dummy_args(spec, q_shape, r_shape,
                                        batch_size))
        jax.block_until_ready(out)
    return plan


def warm_grid(spec, params, engine_name: str, points, *,
              with_traceback: bool = True, mode: str = "align") -> int:
    """Warm one plan per ``(bucket, batch_size)`` point; returns the
    number of plans that actually compiled (already-hot points count 0).
    ``bucket`` is the per-pair length pair; char dims come from the
    spec."""
    char = spec.char_shape
    n = 0
    for bucket, batch_size in points:
        plan = warm_plan(
            spec, params, engine_name, (bucket[0],) + char,
            (bucket[1],) + char, batch_size=batch_size,
            with_traceback=with_traceback, mode=mode)
        n += plan.hits == 0 and plan.calls <= 1
    return n
