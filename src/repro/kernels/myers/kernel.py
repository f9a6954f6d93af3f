"""Pallas TPU kernel for the Myers bit-vector column sweep.

Hardware mapping: pairs are VPU lanes.  One whole query column of DP
cells is delta-encoded in ``n_words`` 32-bit VP/VN words per pair (TPU
vector units carry no 64-bit ints), held as ``(n_words, lanes)`` VMEM
scratch, and the reference streams through a ``fori_loop`` one column
per step — the systolic character stream of the wavefront kernel, except
each "PE" is a machine word covering 32 DP rows of bitwise ops, and 128
pairs advance together, one per lane.

The word loop is unrolled in Python (``n_words`` is static and small:
a 512-bucket is 16 words); words couple only through the horizontal
delta ``hin``/``hout``, a lane vector, so the unrolled chain is a short
recurrence over vector registers, not a carry chain.  The per-column Eq
gather is hoisted to XLA (ops.py builds the ``(R, n_words, B)`` column
table), keeping the kernel free of dynamic gathers.  The grid's second
axis streams the table in column blocks, so the VMEM footprint does not
grow with the reference bucket.

Each lane stops at its own ``r_len`` (later columns leave its score
alone) but the kernel does not replicate the XLA engine's k-threshold
early exit; ops.py applies the same k-saturation sentinel to the result,
so the two variants agree bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD_BITS = 32
LANES = 128
_WT = jnp.uint32
# eq-table bytes streamed per grid step
_BLOCK_BYTES = 2 << 20


def _advance(hin, vp, vn, eq):
    """One 32-bit word of one column for every lane (the lane-vector
    form of ``core.myers._advance_word``)."""
    one = jnp.asarray(1, _WT)
    zero = jnp.asarray(0, _WT)
    hin_neg = jnp.where(hin < 0, one, zero)
    hin_pos = jnp.where(hin > 0, one, zero)
    xv = eq | vn
    eq = eq | hin_neg
    xh = (((eq & vp) + vp) ^ vp) | eq
    ph = vn | ~(xh | vp)
    mh = vp & xh
    top = jnp.asarray(WORD_BITS - 1, _WT)
    hout = ((ph >> top) & one).astype(jnp.int32) - \
        ((mh >> top) & one).astype(jnp.int32)
    ph_s = (ph << 1) | hin_pos
    mh_s = (mh << 1) | hin_neg
    vp_out = mh_s | ~(xv | ph_s)
    vn_out = ph_s & xv
    return hout, vp_out, vn_out, ph, mh


def column_block(r_bucket: int, n_words: int) -> int:
    """Columns per grid step: the largest power of two dividing the
    bucket whose eq block stays within ``_BLOCK_BYTES``."""
    per_col = -(-n_words // 8) * 8 * LANES * 4
    rc = 1
    while (rc * 2 <= r_bucket and r_bucket % (rc * 2) == 0
           and rc * 2 * per_col <= _BLOCK_BYTES):
        rc *= 2
    return rc


def _kernel_body(glob, n_words, sent, rc,
                 lens_ref, eq_ref, score_ref, best_ref, bj_ref,
                 vp_ref, vn_ref, acc_ref):
    wb = WORD_BITS
    q_len = lens_ref[0:1, :]                      # (1, lanes)
    r_len = lens_ref[1:2, :]
    k = pl.program_id(1)
    sw = jnp.clip((q_len - 1) // wb, 0, n_words - 1)
    sb = jnp.clip((q_len - 1) % wb, 0, wb - 1).astype(_WT)
    hin0 = jnp.int32(1) if glob else jnp.int32(0)
    one = jnp.asarray(1, _WT)

    @pl.when(k == 0)
    def _():
        vp_ref[...] = jnp.full(vp_ref.shape, ~jnp.asarray(0, _WT))
        vn_ref[...] = jnp.zeros(vn_ref.shape, _WT)
        acc_ref[0:1, :] = q_len
        acc_ref[1:2, :] = jnp.full(q_len.shape, sent, jnp.int32)
        acc_ref[2:3, :] = jnp.zeros(q_len.shape, jnp.int32)

    def col(jj, carry):
        score, best, bj = carry
        j = k * rc + jj
        eq_col = eq_ref[jj]                       # (n_words, lanes)
        hin = jnp.broadcast_to(hin0, q_len.shape)
        inc = jnp.zeros(q_len.shape, jnp.int32)
        for w in range(n_words):                  # static unroll
            hout, vpo, vno, ph, mh = _advance(
                hin, vp_ref[w:w + 1, :], vn_ref[w:w + 1, :],
                eq_col[w:w + 1, :])
            vp_ref[w:w + 1, :] = vpo
            vn_ref[w:w + 1, :] = vno
            d = ((ph >> sb) & one).astype(jnp.int32) - \
                ((mh >> sb) & one).astype(jnp.int32)
            inc = jnp.where(sw == w, d, inc)
            hin = hout
        live = j < r_len
        score = jnp.where(live, score + inc, score)
        if not glob:
            upd = live & (score < best)           # strict: first argmin wins
            best = jnp.where(upd, score, best)
            bj = jnp.where(upd, j + 1, bj)
        return score, best, bj

    carry = (acc_ref[0:1, :], acc_ref[1:2, :], acc_ref[2:3, :])
    score, best, bj = jax.lax.fori_loop(0, rc, col, carry)
    acc_ref[0:1, :] = score
    acc_ref[1:2, :] = best
    acc_ref[2:3, :] = bj

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        score_ref[...] = score
        best_ref[...] = best
        bj_ref[...] = bj


def myers_fill(eq_cols, lens, *, glob: bool, n_words: int, sent: int,
               interpret: bool = False):
    """Launch the column sweep over a batch of pairs.

    ``eq_cols``: (B, R, n_words) uint32 per-column match words (ops.py
    gathers ``peq[ref[j]]``); ``lens``: (B, 2) int32 ``[q_len, r_len]``.
    Returns (score, best, bj), each (B,) int32 — corner score, last-row
    minimum and its first-argmin column.
    """
    B, R = eq_cols.shape[0], eq_cols.shape[1]
    bt = B if B <= LANES else LANES
    Bp = -(-B // bt) * bt
    eq = jnp.transpose(eq_cols.astype(_WT), (1, 2, 0))       # (R, nw, B)
    lens = jnp.asarray(lens, jnp.int32).T                   # (2, B)
    if Bp != B:
        eq = jnp.pad(eq, ((0, 0), (0, 0), (0, Bp - B)))
        lens = jnp.pad(lens, ((0, 0), (0, Bp - B)))
    rc = column_block(R, n_words)
    kernel = functools.partial(_kernel_body, glob, n_words, sent, rc)
    lane_out = pl.BlockSpec((1, bt), lambda t, k: (0, t))
    fn = pl.pallas_call(
        kernel,
        grid=(Bp // bt, R // rc),
        in_specs=[
            pl.BlockSpec((2, bt), lambda t, k: (0, t)),              # lens
            pl.BlockSpec((rc, n_words, bt), lambda t, k: (k, 0, t)),  # eq
        ],
        out_specs=[lane_out, lane_out, lane_out],
        out_shape=[jax.ShapeDtypeStruct((1, Bp), jnp.int32)] * 3,
        scratch_shapes=[pltpu.VMEM((n_words, bt), _WT),
                        pltpu.VMEM((n_words, bt), _WT),
                        pltpu.VMEM((3, bt), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="myers_fill",
    )
    return tuple(o[0, :B] for o in fn(lens, eq))
