"""Pallas TPU flash-attention kernel (blockwise online softmax in VMEM).

EXPERIMENTS.md §Roofline finds every attention cell memory-bound in the
pure-XLA lowering because the online-softmax accumulator round-trips HBM
once per (q, k) block pair.  Here the accumulator, row-max and row-sum
live in VMEM scratch across the sequential k-block grid dimension — HBM
traffic drops to one read of q/k/v and one write of out, the flash ideal.

Grid: (BH, n_q_blocks, n_k_blocks); the last dimension is sequential on
TPU ('arbitrary'), so scratch persists across k blocks of one q block.
Causal/window masking prunes whole blocks with pl.when — the same static
banding the blockwise XLA path uses (paper §2.2.4).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _body(blk, nk, causal, window, scale, k_len,
          q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = qi * blk
    k_lo = ki * blk
    # whole-block pruning: outside the causal triangle / band -> skip
    live = True
    if causal:
        live = k_lo <= q_lo + blk - 1
    if window is not None:
        live = jnp.logical_and(live, k_lo + blk - 1 > q_lo - window) \
            if causal else (k_lo + blk - 1 > q_lo - window)

    @pl.when(live)
    def _():
        q = q_ref[0].astype(F32)                      # (blk, hd)
        k = k_ref[0].astype(F32)
        v = v_ref[0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        qpos = q_lo + jax.lax.iota(jnp.int32, blk)[:, None]
        kpos = k_lo + jax.lax.iota(jnp.int32, blk)[None, :]
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if k_len is not None:
            mask &= kpos < k_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                           # (blk, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_fill(q, k, v, *, causal: bool, window=None, blk: int = 512,
               k_len=None, scale=None, interpret: bool = False):
    """q/k/v: (BH, S, hd) — same head count (GQA broadcast by the caller).
    Returns out (BH, S, hd), same dtype as q."""
    BH, S, hd = q.shape
    blk = min(blk, S)
    assert S % blk == 0, (S, blk)
    nq = nk = S // blk
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qspec = pl.BlockSpec((1, blk, hd), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, blk, hd), lambda b, i, j: (b, j, 0))
    fn = pl.pallas_call(
        functools.partial(_body, blk, nk, causal, window, scale, k_len),
        grid=(BH, nq, nk),
        in_specs=[qspec, kspec, kspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, hd), F32),
                        pltpu.VMEM((blk, 1), F32),
                        pltpu.VMEM((blk, 1), F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )
    return fn(q, k, v)
