"""Pallas TPU kernel for the DP matrix fill — the back-end §5.1/§5.2.

Hardware mapping (FPGA -> TPU):
  * the N_PE linear systolic array is the 128-lane axis of a VPU vector:
    every per-PE value is a ``(1, N_PE)`` lane vector, and one wavefront
    of N_PE cells is evaluated per inner-loop step;
  * the grid is ``(pair, strip)``: grid step ``(b, c)`` processes the
    strip of query rows ``[c*N_PE, (c+1)*N_PE)`` of pair ``b``.  The strip
    axis is sequential, so the VMEM scratch ``top`` carries a strip's
    bottom row to the next strip — the paper's Preserved Row Score Buffer;
  * the reference streams through the lanes one position per wavefront
    (the systolic character stream).  The host skews it once per pair
    (``r_skew[w, l] = ref[w - l]``), so each wavefront is one row load;
  * neighbour scores move one PE down the array with a lane rotation
    (``pltpu.roll``); lane 0 takes its ``up``/``diag`` inputs from the
    rotated preserved-row buffer, whose row ``w`` holds, in its last lane,
    the previous strip's bottom-row cell that lane 0 needs at wavefront
    ``w - N_PE + 1``;
  * traceback pointers of ``per_word`` consecutive wavefronts are packed
    into one int32 word per lane and stored as one lane vector — the
    address-coalesced TB memory (all PEs hit the same address in
    different banks);
  * per-lane running best + final host-side reduction is the per-PE local
    max and reduction tree of §5.2.

Pair lengths live in SMEM, as do the kernel's scalar parameters.  Every
VMEM block is either a whole array or ends in ``(rows, N_PE)`` with the
full extent of the array, which is what the TPU's (8, 128) tiling needs.
N_PE should be a multiple of the 128-lane VPU width on hardware (any
value >= 2 works in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spec_utils import band_mask, region_mask
from repro.core.traceback import word_layout

def fill_geometry(n_pe: int, r_bucket: int, tb_pack: int) -> tuple[int, int]:
    """``(n_groups, top_rows)``: packed tb words per lane, and rows of the
    preserved-row buffer, for one strip over a reference bucket."""
    _, per_word = word_layout(tb_pack)
    n_groups = -(-(n_pe + r_bucket - 1) // per_word)
    top_rows = -(-(n_groups * per_word + n_pe - 1) // 8) * 8
    return n_groups, top_rows


def _lane_pe(spec, n_pe):
    """Evaluate ``spec.pe`` across the strip.  Scalar alphabets broadcast
    the PE over ``(1, N_PE)`` lane vectors, which is what compiles for the
    TPU; vector alphabets (profiles, complex signals) vmap it per lane."""
    L = spec.n_layers

    def lanes(params, q, r, diag, up, left, i, j):
        if spec.char_shape == ():
            scores, ptr = spec.pe(params, q, r, jnp.stack(diag),
                                  jnp.stack(up), jnp.stack(left), i, j)
            scores = jnp.asarray(scores).reshape(L, 1, n_pe)
            return ([scores[k] for k in range(L)],
                    jnp.broadcast_to(jnp.asarray(ptr), (1, n_pe)))
        cd = spec.char_shape
        col = lambda vs: jnp.concatenate(vs, axis=0).T    # (N_PE, L)
        scores, ptr = jax.vmap(spec.pe, in_axes=(None,) + (0,) * 7)(
            params, q.T.reshape((n_pe,) + cd), r.T.reshape((n_pe,) + cd),
            col(diag), col(up), col(left), i[0], j[0])
        scores = scores.reshape(n_pe, L).T
        return ([scores[k][None] for k in range(L)],
                jnp.broadcast_to(ptr, (n_pe,))[None])
    return lanes


def _kernel_body(spec, n_pe, tb_pack, n_groups, treedef, smem_leaf,
                 leaf_shapes, lens_ref, q_ref, r_ref, row0_ref, colb_ref,
                 *rest):
    n_params = len(leaf_shapes)
    param_refs = rest[:n_params]
    outs = rest[n_params:-1]
    top = rest[-1]
    with_tb = spec.traceback is not None
    if with_tb:
        tb_ref, best_ref, bestj_ref = outs
    else:
        best_ref, bestj_ref = outs

    L = spec.n_layers
    dt = spec.score_dtype
    sent = spec.sentinel()
    width, per_word = word_layout(tb_pack)
    slot_mask = (1 << width) - 1

    leaves = []
    for ref, in_smem, shp in zip(param_refs, smem_leaf, leaf_shapes):
        leaves.append(ref[0] if in_smem else ref[...].reshape(shp))
    params = jax.tree.unflatten(treedef, leaves)

    b = pl.program_id(0)
    c = pl.program_id(1)
    q_len = lens_ref[2 * b]
    r_len = lens_ref[2 * b + 1]

    @pl.when(c == 0)
    def _():
        top[...] = row0_ref[...]

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_pe), 1)
    i_glob = c * n_pe + lane + 1              # global DP row per lane
    q_lanes = q_ref[...]                      # (C, N_PE)
    colb = [colb_ref[k:k + 1, :] for k in range(L)]
    colb_down = [pltpu.roll(v, 1, 1) for v in colb]
    pe = _lane_pe(spec, n_pe)
    head = lane == 0

    def top_row(row):
        return [pltpu.roll(top[k, pl.ds(row, 1), :], 1, 1) for k in range(L)]

    def wavefront(w, s, carry):
        prev2, prev, top_diag, acc, best_v, bestj_v = carry
        j = w - lane + 1                      # column per lane
        on_col0 = lane == w                   # lanes with j == 1
        r_lanes = (r_ref[0, pl.ds(w, 1), :] if q_lanes.shape[0] == 1 else
                   r_ref[:, pl.ds(w, 1), :].reshape(-1, n_pe))
        top_up = top_row(w + n_pe - 1)
        up = [jnp.where(head, t, pltpu.roll(p, 1, 1))
              for t, p in zip(top_up, prev)]
        diag = [jnp.where(head, t, jnp.where(on_col0, cb,
                                             pltpu.roll(p, 1, 1)))
                for t, cb, p in zip(top_diag, colb_down, prev2)]
        left = [jnp.where(on_col0, cb, p) for cb, p in zip(colb, prev)]

        scores, ptr = pe(params, q_lanes, r_lanes, diag, up, left, i_glob, j)
        valid = (j >= 1) & (j <= r_len) & (i_glob <= q_len) & \
            band_mask(spec, i_glob, j)
        cur = [jnp.where(valid, v.astype(dt), sent) for v in scores]
        if with_tb:
            bits = jnp.where(valid, ptr.astype(jnp.int32) & slot_mask, 0)
            acc = acc | (bits << (s * width))

        # preserved-row buffer: the strip's last PE exports column
        # w - N_PE + 2 of its row; the next strip reads it at row w
        for k in range(L):
            top[k, pl.ds(w, 1), :] = cur[k]

        # per-PE local best over the objective region (§5.2); under a
        # sum semiring each lane ⊕-accumulates its region mass instead
        # (sentinel candidates underflow to no-ops) and the host-side
        # reduction logsumexps the lanes
        rmask = region_mask(spec, i_glob, j, q_len, r_len)
        cand = jnp.where(rmask, cur[spec.primary_layer], sent)
        if spec.is_sum:
            best_v = spec.combine(best_v, cand)
        else:
            upd = spec.better(cand, best_v)
            best_v = jnp.where(upd, cand, best_v)
            bestj_v = jnp.where(upd, j, bestj_v)
        return prev, cur, top_up, acc, best_v, bestj_v

    def group(g, carry):
        carry = carry[:3] + (jnp.zeros((1, n_pe), jnp.int32),) + carry[4:]
        for s in range(per_word):             # unrolled: one tb word
            carry = wavefront(g * per_word + s, s, carry)
        if with_tb:
            tb_ref[pl.ds(g, 1), :] = carry[3]
        return carry

    dead = [jnp.full((1, n_pe), sent, dt) for _ in range(L)]
    init = (dead, dead, top_row(n_pe - 2), jnp.zeros((1, n_pe), jnp.int32),
            jnp.full((1, n_pe), sent, dt), jnp.zeros((1, n_pe), jnp.int32))
    carry = jax.lax.fori_loop(0, n_groups, group, init)
    best_ref[...] = carry[4]
    bestj_ref[...] = carry[5]
    # the next strip's top-left corner is init_col at its first row - 1,
    # which is the last lane of this strip's left boundary
    for k in range(L):
        top[k, pl.ds(n_pe - 2, 1), :] = colb[k]


def _chars(x, n_char_dims):
    """Flatten the char dims of ``(..., n, *char)`` into one trailing
    axis ``C`` and widen to a 32-bit type: ``(..., n, C)``."""
    lead = x.shape[:x.ndim - n_char_dims]
    x = x.reshape(lead + (-1,))
    wide = jnp.float32 if jnp.issubdtype(x.dtype, jnp.floating) else jnp.int32
    return x.astype(wide)


def wavefront_fill(spec, params, queries, refs, lens, n_pe: int = 128,
                   interpret: bool = False, tb_pack: int = 1):
    """Launch the matrix-fill kernel over a batch of pairs.

    ``queries`` ``(B, Q, *char)`` with Q a multiple of ``n_pe``; ``refs``
    ``(B, R, *char)``; ``lens`` ``(B, 2)`` int32 ``[q_len, r_len]``.
    Returns ``(tb, best, best_j)``: best/best_j ``(B, C, N_PE)`` and, for
    kernels with a traceback, tb ``(B, C, n_groups, N_PE)`` int32 —
    wavefront ``w`` of lane ``l`` in word ``w // per_word``, slot
    ``w % per_word`` (see :func:`repro.core.traceback.word_layout`); tb is None otherwise.
    """
    B, Q, R = queries.shape[0], queries.shape[1], refs.shape[1]
    if Q % n_pe:
        raise ValueError(f"query bucket {Q} is not a multiple of n_pe={n_pe}")
    n_chunks = Q // n_pe
    L = spec.n_layers
    dt = spec.score_dtype
    sent = spec.sentinel()
    nc = len(spec.char_shape)
    n_groups, top_rows = fill_geometry(n_pe, R, tb_pack)
    _, per_word = word_layout(tb_pack)
    W = n_groups * per_word

    # query strip per (pair, strip): (B, n_chunks, C, N_PE)
    q = _chars(queries, nc).reshape(B, n_chunks, n_pe, -1)
    q = jnp.swapaxes(q, 2, 3)
    C = q.shape[2]
    # skewed reference stream: r_skew[b, :, w, l] = ref[b, w - l]
    r = _chars(refs, nc)                                    # (B, R, C)
    w_idx = jnp.arange(W, dtype=jnp.int32)[:, None]
    l_idx = jnp.arange(n_pe, dtype=jnp.int32)[None, :]
    src = w_idx - l_idx
    inside = (src >= 0) & (src < R)
    r_skew = jnp.take(r, jnp.clip(src, 0, R - 1), axis=1)   # (B, W, N, C)
    r_skew = jnp.where(inside[None, :, :, None], r_skew, 0)
    r_skew = jnp.transpose(r_skew, (0, 3, 1, 2))            # (B, C, W, N)

    # preserved-row buffer seed: row t holds init_row[t - N_PE + 2]
    t_idx = jnp.arange(top_rows, dtype=jnp.int32) - (n_pe - 2)
    row0 = jnp.asarray(spec.init_row(params, jnp.clip(t_idx, 0, R)),
                       dt).reshape(top_rows, L)
    row0 = jnp.where(((t_idx >= 0) & (t_idx <= R))[:, None], row0, sent)
    row0 = jnp.broadcast_to(row0.T[:, :, None], (L, top_rows, n_pe))
    # per-strip left boundary: colb[c, k, l] = init_col[c*N_PE + l + 1]
    i_idx = jnp.arange(1, Q + 1, dtype=jnp.int32)
    colb = jnp.asarray(spec.init_col(params, i_idx), dt).reshape(
        n_chunks, n_pe, L)
    colb = jnp.swapaxes(colb, 1, 2)

    leaves, treedef = jax.tree.flatten(params)
    leaf_shapes = tuple(jnp.shape(l) for l in leaves)
    smem_leaf = tuple(int(jnp.size(l)) == 1 for l in leaves)
    leaves_in = [jnp.reshape(jnp.asarray(l), (1,)) if sm else
                 jnp.atleast_1d(jnp.asarray(l))
                 for l, sm in zip(leaves, smem_leaf)]
    lens = jnp.asarray(lens, jnp.int32).reshape(-1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        smem,                                                    # lens
        pl.BlockSpec((None, None, C, n_pe), lambda b, c: (b, c, 0, 0)),
        pl.BlockSpec((None, C, W, n_pe), lambda b, c: (b, 0, 0, 0)),
        pl.BlockSpec((L, top_rows, n_pe), lambda b, c: (0, 0, 0)),
        pl.BlockSpec((None, L, n_pe), lambda b, c: (c, 0, 0)),
    ] + [smem if sm else
         pl.BlockSpec(l.shape, functools.partial(
             lambda nd, b, c: (0,) * nd, l.ndim))
         for l, sm in zip(leaves_in, smem_leaf)]

    lane_block = pl.BlockSpec((None, None, 1, n_pe),
                              lambda b, c: (b, c, 0, 0))
    out_specs = [lane_block, lane_block]
    out_shapes = [jax.ShapeDtypeStruct((B, n_chunks, 1, n_pe), dt),
                  jax.ShapeDtypeStruct((B, n_chunks, 1, n_pe), jnp.int32)]
    if spec.traceback is not None:
        out_specs.insert(0, pl.BlockSpec((None, None, n_groups, n_pe),
                                         lambda b, c: (b, c, 0, 0)))
        out_shapes.insert(0, jax.ShapeDtypeStruct(
            (B, n_chunks, n_groups, n_pe), jnp.int32))

    from . import ops
    vmem = ops.vmem_bytes(spec, Q, R, params, n_pe=n_pe, tb_pack=tb_pack)
    kernel = functools.partial(_kernel_body, spec, n_pe, tb_pack, n_groups,
                               treedef, smem_leaf, leaf_shapes)
    fn = pl.pallas_call(
        kernel,
        grid=(B, n_chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((L, top_rows, n_pe), dt)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=ops.vmem_limit(vmem)),
        name=f"dp_fill_{spec.name}",
    )
    out = fn(lens, q, r_skew, row0, colb, *leaves_in)
    best, best_j = (o.reshape(B, n_chunks, n_pe) for o in out[-2:])
    tb = out[0] if spec.traceback is not None else None
    return tb, best, best_j
