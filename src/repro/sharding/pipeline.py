"""GPipe-style pipeline parallelism over a 'pipe' mesh axis (shard_map).

Stage s holds layer-slice s of the stacked params; microbatches march
through stages with one ``collective_permute`` per tick (the classic
systolic schedule — the same wavefront idea as the paper's PE array, with
layers as the pipeline dimension instead of DP rows).  Fill+drain bubbles
are M/(M+P-1) efficient; outputs are collected on the last stage.

Exercised by tests/test_multidevice.py on 8 fake devices; the 40 assigned
dry-run cells use DP x TP x EP as assigned, with PP available for meshes
where cross-pod DP is link-starved (see DESIGN.md §6).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(mesh, axis: str, stage_fn: Callable, stage_params,
                   microbatches):
    """stage_params: pytree, leaves (P_stages, ...) sharded over ``axis``;
    microbatches: (M, mb, ...) replicated along ``axis``.
    Returns (M, mb, ...) outputs (from the final stage).
    """
    n_stages = mesh.shape[axis]
    M = microbatches.shape[0]
    n_axes = len(microbatches.shape)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    xspec = P(*([None] * n_axes))
    ospec = P(axis, *([None] * n_axes))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(pspec, xspec), out_specs=ospec, check_vma=False)
    def run(params_local, xs):
        sid = jax.lax.axis_index(axis)
        params_one = jax.tree.map(lambda t: t[0], params_local)
        perm = [(i, i + 1) for i in range(n_stages - 1)]
        mb_shape = xs.shape[1:]
        carry = jnp.zeros(mb_shape, xs.dtype)
        outs = jnp.zeros((M,) + mb_shape, xs.dtype)
        for t in range(M + n_stages - 1):
            feed = xs[min(t, M - 1)]
            inp = jnp.where(sid == 0, feed, carry)
            y = stage_fn(params_one, inp)
            # last stage commits microbatch t-(P-1) at tick t
            m_out = t - (n_stages - 1)
            if 0 <= m_out < M:
                commit = (sid == n_stages - 1)
                outs = outs.at[m_out].set(
                    jnp.where(commit, y, outs[m_out]))
            carry = jax.lax.ppermute(y, axis, perm)
        return outs[None]

    return run(stage_params, microbatches)[-1]


def sequential_reference(stage_fn, stage_params, microbatches, n_stages):
    """Oracle: apply the stages in order, no pipelining."""
    def one(x):
        for s in range(n_stages):
            ps = jax.tree.map(lambda t: t[s], stage_params)
            x = stage_fn(ps, x)
        return x
    return jax.vmap(one)(microbatches)
