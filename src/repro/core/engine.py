"""Wavefront (anti-diagonal) back-end engine — pure JAX.

This is the JAX analogue of the DP-HLS back-end (§5.1):

  * the loop over anti-diagonals is the ``#pragma HLS PIPELINE`` wavefront
    loop — *strip-mined*: each step evaluates ``strip`` consecutive
    anti-diagonals with the inner loop unrolled (the canonical
    strip-mine-and-unroll pipeline transform, iteration count
    ⌈(Q+R)/strip⌉), and *early-exiting*: the loop stops at the
    ``q_len + r_len`` wavefront (or the caller's shared ``live_bound``),
    so a pair padded into a 2x bucket never pays the padded cost,
  * the lane dimension (vector of Q+1 cells) is the unrolled PE array
    (``#pragma HLS UNROLL``) — on TPU these become VPU lanes,
  * the two carried diagonal buffers are the fully-partitioned DP memory
    buffers (optimization (e)),
  * the reference sequence *streams* through the lane vector one position
    per wavefront, exactly like characters streaming through the systolic
    array (optimizations (c)/(d)),
  * traceback pointers are emitted one contiguous row per wavefront,
    bit-packed in the loop into int32 words of ``4 * tb_pack`` pointers
    along the lane axis (the address-coalesced traceback memory of §5.2 at
    the kernel's declared ``ptr_bits`` width — a 4x cut in tb memory for
    2-bit FSMs, and a store the TPU keeps and gathers at 32 bits),
  * the masked running best + final reduction is §5.2's per-PE local max
    and reduction tree (corner-region kernels capture their single
    objective cell directly instead of reducing every wavefront).

The user-facing surface is only ``spec.pe`` / ``spec.init_*`` — the engine
body never changes per kernel (the paper's front-end/back-end separation).
``strip=1, tb_pack=1, live_bound=Q+R`` reproduces the seed schedule's
alignments bit for bit.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import types as T
from .spec_utils import band_mask, region_mask
from .traceback import pack_words


# Per-backend default for anti-diagonals per loop step — the single
# source of truth (runtime.registry registers this same dict as the
# wavefront engine's 'strip' option default).  On accelerators the
# sequential loop pays a per-step dispatch the strip amortizes (the
# paper's pipelined wavefront loop); XLA:CPU compiles the unrolled body
# to measurably *worse* code (the fill is memory-bound on the lane
# buffers and bigger loop bodies defeat its fusion), so the CPU default
# keeps the seed schedule.
STRIP_DEFAULTS = {"cpu": 1, "default": 8}


def default_strip() -> int:
    """``STRIP_DEFAULTS`` resolved against the active backend."""
    return STRIP_DEFAULTS.get(jax.default_backend(),
                              STRIP_DEFAULTS["default"])


def resolve_tb_pack(spec: T.DPKernelSpec, tb_pack: Optional[int]) -> int:
    """Validate/resolve a pointers-per-byte request against the kernel's
    declared pointer width (``None`` -> the spec's natural packing)."""
    pack = spec.tb_pack if tb_pack is None else int(tb_pack)
    if pack not in (1, 2, 4, 8):
        raise ValueError(f"tb_pack must be 1, 2, 4 or 8, got {pack}")
    if spec.traceback is not None and 8 // pack < spec.ptr_bits:
        raise ValueError(
            f"tb_pack={pack} leaves {8 // pack}-bit slots but kernel "
            f"{spec.name} declares ptr_bits={spec.ptr_bits}")
    return pack


def run(spec: T.DPKernelSpec, params, query, ref, q_len=None, r_len=None,
        *, strip: Optional[int] = None, tb_pack: Optional[int] = None,
        live_bound=None, xdrop: Optional[int] = None) -> T.DPResult:
    Q = query.shape[0]
    R = ref.shape[0]
    L = spec.n_layers
    dt = spec.score_dtype
    sent = spec.sentinel()
    q_len = jnp.asarray(Q if q_len is None else q_len, jnp.int32)
    r_len = jnp.asarray(R if r_len is None else r_len, jnp.int32)
    with_tb = spec.traceback is not None
    strip = default_strip() if strip is None else int(strip)
    if strip < 1:
        raise ValueError(f"strip must be >= 1, got {strip}")
    pack = resolve_tb_pack(spec, tb_pack)
    if xdrop is not None and spec.is_sum:
        raise ValueError(
            "xdrop prunes by a running best score; sum-semiring kernels "
            "have no best to drop from")

    lanes = Q + 1
    i_idx = jnp.arange(lanes, dtype=jnp.int32)

    # Boundary scores (front-end step 2).
    row0 = jnp.asarray(spec.init_row(params, jnp.arange(R + 1, dtype=jnp.int32)),
                       dt).reshape(R + 1, L)
    col0 = jnp.asarray(spec.init_col(params, i_idx), dt).reshape(lanes, L)
    col0 = jnp.where((i_idx[:, None] <= q_len) & band_mask(spec, i_idx, 0)[:, None],
                     col0, sent)

    # Lane-resident query characters: lane i holds q[i-1] (lane 0 is the
    # boundary row).  Mirrors each PE latching its query base (§5.1).
    q_lane = jnp.concatenate([query[:1], query], axis=0)  # lane 0 value unused

    # Reference stream: r_diag[i] at diagonal d holds ref[d-1-i].
    cd = spec.char_shape
    r_diag0 = jnp.zeros((lanes,) + cd, spec.char_dtype)

    vpe = jax.vmap(spec.pe, in_axes=(None, 0, 0, 0, 0, 0, 0, 0))

    def step(carry, d):
        """One anti-diagonal — the seed schedule, unchanged."""
        if xdrop is None:
            prev2, prev, r_stream, best, bi, bj = carry
        else:
            prev2, prev, r_stream, best, bi, bj, xbest = carry
        # stream one reference char into lane 0
        new_char = jax.lax.dynamic_index_in_dim(
            ref, jnp.clip(d - 1, 0, R - 1), axis=0, keepdims=False)
        r_stream = jnp.concatenate([new_char[None], r_stream[:-1]], axis=0)

        j = d - i_idx  # column per lane
        diag_v = jnp.concatenate([jnp.full((1, L), sent, dt), prev2[:-1]], axis=0)
        up_v = jnp.concatenate([jnp.full((1, L), sent, dt), prev[:-1]], axis=0)
        left_v = prev

        scores, ptr = vpe(params, q_lane, r_stream, diag_v, up_v, left_v, i_idx, j)
        scores = jnp.asarray(scores, dt).reshape(lanes, L)
        ptr = jnp.asarray(ptr, jnp.uint8).reshape(lanes)

        interior = (i_idx >= 1) & (j >= 1) & (i_idx <= q_len) & (j <= r_len)
        valid = interior & band_mask(spec, i_idx, j)
        newbuf = jnp.where(valid[:, None], scores, sent)
        # boundary row (lane 0) and boundary column (lane i == d)
        row_b = jax.lax.dynamic_index_in_dim(row0, jnp.clip(d, 0, R), 0, keepdims=False)
        on_row0 = (i_idx == 0) & (d <= r_len) & band_mask(spec, 0, d)
        on_col0 = (i_idx == d) & (d <= q_len)
        newbuf = jnp.where(on_row0[:, None], row_b[None, :], newbuf)
        newbuf = jnp.where(on_col0[:, None], col0, newbuf)

        if xdrop is not None:
            # X-drop adaptive band: cells whose primary-layer score falls
            # more than ``xdrop`` behind the running best over *all*
            # computed cells go sentinel — downstream neighbors read a
            # dead cell and the live band shrinks per pair.  Approximate
            # by design (a pruned cell could in principle have fed a
            # comeback path); the fill terminates once no live cell
            # remains (see ``cond`` below).
            prim = newbuf[:, spec.primary_layer]
            xbest = spec.combine(xbest, spec.reduce_best(prim))
            thr = xbest + xdrop if spec.is_min else xbest - xdrop
            newbuf = jnp.where(spec.better(thr, prim)[:, None], sent, newbuf)

        # §5.2 local-max bookkeeping over the objective region.
        if spec.region == T.REGION_CORNER and not spec.is_sum:
            # the region is the single cell (q_len, r_len) on diagonal
            # q_len + r_len: capture it directly instead of reducing +
            # arg-reducing the whole lane vector every step (bit-
            # identical — the masked reduction could only ever fire
            # there, and newbuf already carries the validity masking)
            cell = jax.lax.dynamic_index_in_dim(
                newbuf, jnp.clip(q_len, 0, lanes - 1), 0,
                keepdims=False)[spec.primary_layer]
            upd = (d == q_len + r_len) & (q_len >= 1) & (r_len >= 1) & \
                spec.better(cell, best)
            best = jnp.where(upd, cell, best)
            bi = jnp.where(upd, q_len, bi)
            bj = jnp.where(upd, r_len, bj)
        elif spec.is_sum:
            # sum semiring: ⊕-accumulate the whole region's mass across
            # wavefronts (this diagonal's logsumexp folded into the
            # running total).  Sentinel candidates underflow bit-exactly,
            # so dead diagonals are no-ops; end cells carry no path
            # meaning under a sum and stay 0.
            rmask = region_mask(spec, i_idx, j, q_len, r_len)
            cand = jnp.where(rmask, newbuf[:, spec.primary_layer], sent)
            best = spec.combine(best, spec.reduce_best(cand))
        else:
            rmask = region_mask(spec, i_idx, j, q_len, r_len)
            cand = jnp.where(rmask, newbuf[:, spec.primary_layer], sent)
            lane_best = spec.reduce_best(cand)
            lane_arg = spec.arg_best(cand).astype(jnp.int32)
            upd = spec.better(lane_best, best)
            best = jnp.where(upd, lane_best, best)
            bi = jnp.where(upd, lane_arg, bi)
            bj = jnp.where(upd, d - lane_arg, bj)

        tb_row = jnp.where(valid, ptr, jnp.uint8(0)) if with_tb else None
        out = (prev, newbuf, r_stream, best, bi, bj)
        if xdrop is not None:
            out = out + (xbest,)
        return out, tb_row

    def body(carry, d0):
        # strip-mined: 'strip' consecutive anti-diagonals per scan step,
        # unrolled so XLA fuses their PE evaluations into one dispatch
        rows = []
        for k in range(strip):
            carry, tb_row = step(carry, d0 + k)
            if with_tb:
                rows.append(tb_row)
        return carry, (pack_words(jnp.stack(rows), pack) if with_tb else None)

    # d = 0 buffer: only lane 0 (cell (0,0)) is defined.
    buf_d0 = jnp.full((lanes, L), sent, dt)
    buf_d0 = buf_d0.at[0].set(jnp.where(band_mask(spec, 0, 0), row0[0], sent))
    buf_dm1 = jnp.full((lanes, L), sent, dt)

    n_steps = -(-(Q + R) // strip)
    # Early-exit bound: diagonals beyond q_len + r_len hold no live cell
    # (every mask requires i <= q_len, j <= r_len, so d = i + j is
    # bounded) — a 40-base pair padded into a 64-bucket stops after 80
    # wavefronts, not 128.  Untouched trailing tb rows stay zero, exactly
    # what the masked store would have written.  A batched caller passes
    # ``live_bound = max(q_lens + r_lens)`` with vmap ``in_axes=None``:
    # the loop counter then stays unbatched, the whole block exits at the
    # batch-max bound, and the tb write keeps its scalar (in-place)
    # start index — a per-row bound would turn it into a scatter that
    # copies the store every step.
    if live_bound is None:
        live_bound = q_len + r_len
    live_steps = jnp.minimum(
        (jnp.asarray(live_bound, jnp.int32) + strip - 1) // strip,
        jnp.int32(n_steps))
    n_words = -(-lanes // (4 * pack))
    tb0 = jnp.zeros((n_steps * strip, n_words), jnp.int32) if with_tb else None

    def cond(state):
        s = state[0]
        ok = s < live_steps
        if xdrop is not None:
            # stop once neither of the two carried diagonals holds a live
            # cell (d+1 reads prev for up/left *and* prev2 for diag, so
            # both must be dead before no new cell can come alive)
            live = jnp.any(spec.better(state[1][0][:, spec.primary_layer],
                                       sent)) | \
                jnp.any(spec.better(state[1][1][:, spec.primary_layer],
                                    sent))
            ok = ok & live
        return ok

    def wbody(state):
        s, carry, tb_buf = state
        carry, rows = body(carry, s * strip + 1)
        if with_tb:
            tb_buf = jax.lax.dynamic_update_slice(
                tb_buf, rows, (s * strip, jnp.int32(0)))
        return s + 1, carry, tb_buf

    carry0 = (buf_dm1, buf_d0, r_diag0, sent, jnp.int32(0), jnp.int32(0))
    if xdrop is not None:
        carry0 = carry0 + (sent,)
    _, final_carry, tb = jax.lax.while_loop(
        cond, wbody, (jnp.int32(0), carry0, tb0))
    best, bi, bj = final_carry[3], final_carry[4], final_carry[5]
    return T.DPResult(score=best, end_i=bi, end_j=bj, tb=tb,
                      tb_layout=("diag", pack))
