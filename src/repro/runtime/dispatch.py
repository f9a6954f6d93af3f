"""Packed batch dispatch: variable-length pair workloads -> bucketed plans.

This is the batch entry point of the runtime layer: callers hand over a
list of ``(query, ref)`` pairs of arbitrary lengths and get per-pair
results back in request order.  Internally the pairs are grouped by
``bucketing.pack_by_bucket``, zero-padded to their bucket, and every block
runs through the shared ``CompiledPlan`` cache — so a workload that mixes
buckets (e.g. the read mapper's per-chain extension windows) exercises one
compiled executable per ``(bucket, block)`` instead of one per request.

``run_pipelined`` is the double-buffered dispatcher of DP-HLS §5.3 in
host/device form: *launch* enqueues a batch on the device (JAX async
dispatch returns before the computation finishes) and *harvest* blocks on
its results one batch behind, so the host pads and post-processes batch N
while batch N+1 computes.  ``run_pairs`` and ``serve.AlignmentService``
both drive their batch streams through it.
"""
from __future__ import annotations

import collections
from typing import Callable, Iterable, Optional, Sequence

import jax.numpy as jnp
import numpy as np

import repro.core.traceback as tb_mod
import repro.core.types as T

from repro.obs import trace as obs_trace

from . import bucketing
from . import plan as plan_mod


def run_pipelined(items: Iterable, launch: Callable, harvest: Callable, *,
                  depth: int = 2, on_abandon: Optional[Callable] = None
                  ) -> int:
    """Drive ``launch``/``harvest`` over a batch stream, ``depth - 1``
    launches ahead of the harvests.

    ``launch(item)`` must enqueue device work and return without blocking
    (its return value is handed to ``harvest(item, out)``, which is where
    device->host sync happens).  ``depth=1`` degenerates to the fully
    synchronous launch-then-harvest loop.  On an exception the un-harvested
    window is handed to ``on_abandon(item, out)`` (callers requeue there)
    before the exception propagates; a *launch* failure is the launcher's
    own to clean up — its item never enters the window.  Returns the sum
    of ``harvest`` return values (``None`` counts as 0).
    """
    if depth < 1:
        raise ValueError(f"pipeline depth must be >= 1, got {depth}")
    window: collections.deque = collections.deque()
    total = 0

    def _launch(item):
        with obs_trace.span("dispatch.launch", cat="dispatch"):
            return launch(item)

    def _harvest(it, out):
        with obs_trace.span("dispatch.harvest", cat="dispatch"):
            return harvest(it, out)

    try:
        for item in items:
            window.append((item, _launch(item)))
            while len(window) >= depth:
                it, out = window.popleft()
                total += _harvest(it, out) or 0
        while window:
            it, out = window.popleft()
            total += _harvest(it, out) or 0
    except BaseException:
        if on_abandon is not None:
            while window:
                it, out = window.popleft()
                on_abandon(it, out)
        raise
    return total


def _np_char_dtype(spec):
    return np.dtype(jnp.dtype(spec.char_dtype).name)


def _slice_out(out, i):
    """Row ``i`` of a batched Alignment/DPResult as host-side scalars."""
    def pick(x):
        return None if x is None else np.asarray(x)[i]
    if isinstance(out, T.Alignment):
        return tb_mod.raise_if_truncated(T.Alignment(
            score=pick(out.score), end_i=pick(out.end_i),
            end_j=pick(out.end_j), start_i=pick(out.start_i),
            start_j=pick(out.start_j), moves=pick(out.moves),
            n_moves=pick(out.n_moves), truncated=pick(out.truncated)))
    return T.DPResult(score=pick(out.score), end_i=pick(out.end_i),
                      end_j=pick(out.end_j), tb=pick(out.tb),
                      tb_layout=out.tb_layout)


def run_pairs(spec, params, pairs: Sequence[tuple], *,
              engine_name: str = "wavefront", block: int = 8,
              with_traceback: bool = True, mode: str = "align",
              min_bucket: int = bucketing.DEFAULT_MIN_BUCKET,
              max_bucket: Optional[int] = None,
              pipeline_depth: int = 2) -> list:
    """Run every ``(query, ref)`` pair; results come back in input order.

    Each bucketed block is padded to exactly ``block`` rows (tail rows are
    length-1 dummies) so repeated calls reuse one plan per bucket shape.
    Blocks stream through ``run_pipelined``: padding the next block
    overlaps the device computing the current one (``pipeline_depth=1``
    restores the synchronous path).
    """
    pairs = [(np.asarray(q), np.asarray(r)) for q, r in pairs]
    lengths = [(q.shape[0], r.shape[0]) for q, r in pairs]
    batches, _ = bucketing.pack_by_bucket(lengths, block=block,
                                          min_bucket=min_bucket,
                                          max_bucket=max_bucket)
    char = spec.char_shape
    dtype = _np_char_dtype(spec)
    results: list = [None] * len(pairs)

    def launch(b):
        bq, br = b.bucket
        qs = np.zeros((block, bq) + char, dtype)
        rs = np.zeros((block, br) + char, dtype)
        ql = np.ones((block,), np.int32)
        rl = np.ones((block,), np.int32)
        for row, idx in enumerate(b.indices):
            q, r = pairs[idx]
            ql[row], rl[row] = q.shape[0], r.shape[0]
            qs[row, : ql[row]] = q
            rs[row, : rl[row]] = r
        plan = plan_mod.get_plan(spec, engine_name, (bq,) + char,
                                 (br,) + char, batch_size=block,
                                 with_traceback=with_traceback, mode=mode)
        return plan(params, jnp.asarray(qs), jnp.asarray(rs),
                    jnp.asarray(ql), jnp.asarray(rl))

    def harvest(b, out):
        for row, idx in enumerate(b.indices):
            results[idx] = _slice_out(out, row)

    run_pipelined(batches, launch, harvest, depth=pipeline_depth)
    return results
