"""jit'd wrapper around the Pallas Myers kernel: Peq/column-table prep in
XLA, launch, and the same result contract as ``core.myers.run`` (empty
pairs -> sentinel, k-saturation sentinel, first-argmin search end).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import types as T
from repro.core import myers as M
from repro.kernels.grid_vmap import grid_vmap
from . import kernel as K


def vmem_bytes(spec, q_bucket: int, r_bucket: int, params=None) -> int:
    """Static VMEM footprint of the Myers Pallas kernel at a bucket
    shape: the double-buffered eq-table column block (the dominant term,
    gathered XLA-side and streamed in ``column_block`` columns per grid
    step), the VP/VN word scratch and the lane-vector lengths, results
    and score track, all tile-padded for a full 128-lane tile of pairs.
    Pure shape arithmetic, no trace — the plan linter's budget check."""
    n_words = max(1, -(-int(q_bucket) // K.WORD_BITS))
    rc = K.column_block(max(int(r_bucket), 1), n_words)
    word_tile = -(-n_words // 8) * 8 * K.LANES * 4
    row_tile = 8 * K.LANES * 4
    return (2 * rc * word_tile                # eq_cols block
            + 2 * word_tile                   # VP/VN scratch
            + 2 * row_tile                    # lens block
            + 2 * 3 * row_tile                # score/best/best_j outs
            + row_tile)                       # score-track scratch


def run(spec, params, query, ref, q_len=None, r_len=None,
        interpret: bool = False) -> T.DPResult:
    M._check_spec(spec)
    Q, R = query.shape[0], ref.shape[0]
    q_len = jnp.asarray(Q if q_len is None else q_len, jnp.int32)
    r_len = jnp.asarray(R if r_len is None else r_len, jnp.int32)
    wb = K.WORD_BITS
    n_words = max(1, -(-Q // wb))
    sent = spec.sentinel()
    glob = spec.region == T.REGION_CORNER
    k = jnp.asarray(params.get("max_dist", -1), jnp.int32)
    unlimited = k < 0

    # XLA-side prep: symbol table, then the per-column gather the kernel
    # would otherwise do as a dynamic 2-D load per step
    peq = M.build_peq(query, q_len, n_words, word_dtype=jnp.uint32)
    eq_cols = jnp.take(peq, jnp.clip(ref.astype(jnp.int32), 0,
                                     M.N_SYMBOLS - 1), axis=0)

    # the kernel reports a static min-objective sentinel
    fill = grid_vmap(lambda _, eq, lens: K.myers_fill(
        eq, lens, glob=glob, n_words=n_words, sent=1 << 30,
        interpret=interpret))
    score, best, bj = fill((), eq_cols, jnp.stack([q_len, r_len]))

    raw = score if glob else best
    dist = jnp.where(~unlimited & (raw > k), sent, raw)
    ok = (q_len >= 1) & (r_len >= 1)
    dist = jnp.where(ok, dist, sent)
    live = ok & (dist < sent)
    end_i = jnp.where(live, q_len, jnp.int32(0))
    end_j = jnp.where(live, r_len if glob else bj, jnp.int32(0))
    return T.DPResult(score=dist.astype(spec.score_dtype), end_i=end_i,
                      end_j=end_j, tb=None, tb_layout="diag")
