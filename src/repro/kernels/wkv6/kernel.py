"""Pallas TPU kernel for the WKV6 chunked recurrence.

This is the structural fix identified in EXPERIMENTS.md §Perf cell 1: the
pure-XLA chunk scan round-trips the (c, c, hd) intra-chunk tensors and the
(hd, hd) state through HBM every chunk; here they live in VMEM for the
whole sequence — the DP-HLS preserved-row-buffer discipline (§5.1) applied
to the 1-D data-dependent-decay recurrence.

Grid: (B*H, S / S_BLK); the second dimension is sequential on TPU, so the
VMEM scratch ``state`` carries across sequence blocks of the same (b, h)
row (reset via pl.when at block 0).  Inside a block, a fori_loop walks
CHUNK-sized steps with the exact pairwise log-difference form of
models/mixers._wkv_chunk.

VMEM budget per grid step (S_BLK=2048, hd=64, f32): 4 inputs + 1 output
x (2048, 64, 4B) = 2.6 MiB, state 16 KiB, chunk temporaries (32, 32, 64)
x few = ~1 MiB — comfortably inside ~16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _body(chunk, n_chunks, r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref,
          state_ref):
    sblk = pl.program_id(1)

    @pl.when(sblk == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0]                                       # (hd,)
    c = chunk

    def step(i, state):
        sl = (0, pl.ds(i * c, c), slice(None))
        r = r_ref[sl].astype(F32)                     # (c, hd)
        k = k_ref[sl].astype(F32)
        v = v_ref[sl].astype(F32)
        lw = lw_ref[sl].astype(F32)
        L = jnp.cumsum(lw, axis=0)
        Lq = L - lw
        D_ij = Lq[:, None, :] - L[None, :, :]         # (c, c, hd) in VMEM
        tri = (jax.lax.iota(jnp.int32, c)[:, None]
               > jax.lax.iota(jnp.int32, c)[None, :])[..., None]
        W_ij = jnp.where(tri, jnp.exp(jnp.minimum(D_ij, 0.0)), 0.0)
        A = jnp.einsum("id,ijd,jd->ij", r, W_ij, k,
                       preferred_element_type=F32)
        A = A + jnp.diag(jnp.einsum("id,d,id->i", r, u, k,
                                    preferred_element_type=F32))
        y = A @ v + jnp.einsum("id,dv->iv", r * jnp.exp(Lq), state,
                               preferred_element_type=F32)
        y_ref[sl] = y.astype(y_ref.dtype)
        decay_all = jnp.exp(L[-1])
        k_scaled = k * jnp.exp(L[-1][None, :] - L)
        return decay_all[:, None] * state + k_scaled.T @ v

    state_ref[...] = jax.lax.fori_loop(0, n_chunks, step, state_ref[...])


def wkv6_fill(r, k, v, lw, u, *, s_blk: int = 2048, chunk: int = 32,
              interpret: bool = False):
    """r/k/v/lw: (BH, S, hd); u: (BH, hd) (pre-broadcast per row).
    Returns y: (BH, S, hd) f32."""
    BH, S, hd = r.shape
    s_blk = min(s_blk, S)
    assert S % s_blk == 0 and s_blk % chunk == 0, (S, s_blk, chunk)
    grid = (BH, S // s_blk)
    spec = pl.BlockSpec((1, s_blk, hd), lambda b, s: (b, s, 0))
    uspec = pl.BlockSpec((1, hd), lambda b, s: (b, 0))
    fn = pl.pallas_call(
        functools.partial(_body, chunk, s_blk // chunk),
        grid=grid,
        in_specs=[spec, spec, spec, spec, uspec],
        out_specs=pl.BlockSpec((1, s_blk, hd), lambda b, s: (b, s, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), F32),
        scratch_shapes=[pltpu.VMEM((hd, hd), F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )
    return fn(r, k, v, lw, u)
