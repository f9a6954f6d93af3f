"""FSM traceback executor (paper §5.2, Listings 3/7).

The matrix fill stores traceback pointers packed into int32 words — a
kernel that declares a narrow ``ptr_bits`` gets ``pack`` pointers per
byte's worth of bits, ``4 * pack`` per word — and traceback is a pointer
chase driven by the kernel's FSM: ``(state, ptr) -> (move, next_state)``.
``run`` walks one alignment with a ``lax.while_loop``; ``run_batched``
walks a whole block with one loop over an active mask that exits as soon
as every row has hit its stop cell (instead of paying the worst-case
step count per row).

Pointer stores are layout-dependent:
  * ('diag', pack) (wavefront engine): int32 words tb[(i+j) - 1, i % nw]
    (coalesced, §5.2), lane i in slot i // nw of its word, where
    ``nw = tb.shape[1]`` words cover the lane vector (:func:`pack_words`);
  * 'row' (reference engine): tb[i, j];
  * ('chunk', n_pe, pack) (Pallas kernel): int32 words
    tb[chunk, w // per_word, lane], strip height n_pe, lane = (i-1) % n_pe,
    chunk-local wavefront w = lane + j - 1, slot w % per_word.
Every slot is 8 // pack bits wide; 32-bit words are what the TPU stores
and gathers without widening the whole store.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import types as T


class TracebackTruncated(RuntimeError):
    """The traceback walk ran out of its ``max_len`` step budget before
    reaching a stop cell — the recorded path is a corrupt prefix."""


def word_layout(pack: int) -> tuple[int, int]:
    """``(slot_bits, per_word)``: ``pack`` pointers per byte's worth of
    bits, so ``4 * pack`` pointers per int32 word."""
    if pack not in (1, 2, 4, 8):
        raise ValueError(f"pack must be 1, 2, 4 or 8, got {pack}")
    width = 8 // pack
    return width, 32 // width


def pack_words(ptr, pack: int):
    """Pack pointers along the last axis into int32 words: ``(..., lanes)``
    -> ``(..., nw)`` with ``nw = ceil(lanes / per_word)``; lane i lives in
    word ``i % nw``, slot ``i // nw``.  Slots are contiguous lane slices,
    so packing needs no lane-splitting reshape."""
    width, per_word = word_layout(pack)
    ptr = jnp.asarray(ptr).astype(jnp.int32) & ((1 << width) - 1)
    lanes = ptr.shape[-1]
    nw = -(-lanes // per_word)
    if nw * per_word != lanes:
        ptr = jnp.concatenate(
            [ptr, jnp.zeros(ptr.shape[:-1] + (nw * per_word - lanes,),
                            jnp.int32)], axis=-1)
    acc = ptr[..., :nw]
    for s in range(1, per_word):
        acc = acc | (ptr[..., s * nw:(s + 1) * nw] << (s * width))
    return acc


def word_slot(word, slot, pack: int):
    """Pointer ``slot`` of an int32 word of a ``pack`` store."""
    width, _ = word_layout(pack)
    return (word >> (slot * width)) & ((1 << width) - 1)


def _make_reader(tb, layout):
    """Return ``read(i, j) -> ptr`` for one pointer store layout."""
    if isinstance(layout, tuple) and layout[0] == "chunk":
        _, n_pe, pack = layout
        _, per_word = word_layout(pack)

        def read(i, j):
            c = jnp.clip((i - 1) // n_pe, 0, tb.shape[0] - 1)
            lane = jnp.clip((i - 1) % n_pe, 0, n_pe - 1)
            w = jnp.clip(lane + j - 1, 0, tb.shape[1] * per_word - 1)
            return word_slot(tb[c, w // per_word, lane], w % per_word, pack)
        return read
    if isinstance(layout, tuple) and layout[0] == "diag":
        pack = layout[1]
        _, per_word = word_layout(pack)
        nw = tb.shape[1]

        def read(i, j):
            d = jnp.clip(i + j - 1, 0, tb.shape[0] - 1)
            lane = jnp.clip(i, 0, nw * per_word - 1)
            return word_slot(tb[d, lane % nw], lane // nw, pack)
        return read
    if layout == "row":
        def read(i, j):
            return tb[jnp.clip(i, 0, tb.shape[0] - 1),
                      jnp.clip(j, 0, tb.shape[1] - 1)]
        return read
    raise ValueError(f"unknown tb layout {layout!r}")


def default_max_len(tb_shape, layout) -> int:
    """Safe step budget derived from the pointer store's own (bucketed)
    shape: an upper bound on Q + R, plus one for the terminating cell —
    a walk can never legitimately exceed it."""
    if isinstance(layout, tuple) and layout[0] == "chunk":
        _, n_pe, pack = layout
        q = tb_shape[0] * n_pe
        r = tb_shape[1] * word_layout(pack)[1] - n_pe + 1
        return q + r + 1
    if layout == "row":
        return tb_shape[0] + tb_shape[1]
    # 'diag' layouts store >= Q + R wavefront rows
    return tb_shape[0] + 1


def _fsm_step(tspec, read, i, j, state):
    """One FSM transition shared by the single and batched walkers."""
    stop_here = tspec.stop_fn(i, j)
    ptr = read(i, j).astype(jnp.int32)
    move, nstate = tspec.fsm(state, ptr)
    move = jnp.asarray(move, jnp.int32)
    # Boundary cells are init cells: no pointer was stored.  For kernels
    # that trace to the origin/top row their moves are implicit (row 0
    # walks LEFT, column 0 walks UP); local/overlap kernels instead end
    # the path at the boundary (ptr END / stop condition).
    if tspec.stop in (T.STOP_ORIGIN, T.STOP_TOP_ROW):
        on_row0 = (i == 0) & (j > 0)
        on_col0 = (j == 0) & (i > 0)
        move = jnp.where(on_row0, T.MOVE_LEFT,
                         jnp.where(on_col0, T.MOVE_UP, move))
        nstate = jnp.where(on_row0 | on_col0, state, nstate)
    is_end = jnp.logical_or(stop_here, move == T.MOVE_END)
    di = jnp.where((move == T.MOVE_DIAG) | (move == T.MOVE_UP), 1, 0)
    dj = jnp.where((move == T.MOVE_DIAG) | (move == T.MOVE_LEFT), 1, 0)
    return move, jnp.asarray(nstate, jnp.int32), is_end, di, dj


def run(spec: T.DPKernelSpec, result: T.DPResult,
        max_len: int | None = None) -> T.Alignment:
    """Walk pointers from the optimum cell back to the path start.

    ``moves`` comes out in end->start order; ``n_moves`` gives its
    length.  ``max_len=None`` derives the always-sufficient budget from
    the pointer store shape; an explicit smaller budget that runs out
    sets ``truncated`` on the result (``raise_if_truncated`` turns that
    into an error at host-side harvest instead of silently returning the
    corrupt partial path).
    """
    tspec = spec.traceback
    assert tspec is not None, f"kernel {spec.name} has no traceback"
    if max_len is None:
        max_len = default_max_len(result.tb.shape, result.tb_layout)
    read = _make_reader(result.tb, result.tb_layout)

    def cond(c):
        i, j, state, k, done, moves = c
        return jnp.logical_and(jnp.logical_not(done), k < max_len)

    def body(c):
        i, j, state, k, done, moves = c
        move, nstate, is_end, di, dj = _fsm_step(tspec, read, i, j, state)
        rec = jnp.where(is_end, jnp.int32(T.MOVE_END), move)
        moves = jax.lax.dynamic_update_index_in_dim(
            moves, jnp.where(is_end, jnp.uint8(0), rec.astype(jnp.uint8)), k, 0)
        i2 = jnp.where(is_end, i, i - di)
        j2 = jnp.where(is_end, j, j - dj)
        k2 = jnp.where(is_end, k, k + 1)
        return (i2, j2, nstate, k2, is_end, moves)

    moves0 = jnp.zeros((max_len,), jnp.uint8)
    init = (jnp.asarray(result.end_i, jnp.int32),
            jnp.asarray(result.end_j, jnp.int32),
            jnp.int32(tspec.initial_state), jnp.int32(0),
            jnp.asarray(False), moves0)
    i, j, _, k, done, moves = jax.lax.while_loop(cond, body, init)
    return T.Alignment(score=result.score, end_i=result.end_i, end_j=result.end_j,
                       start_i=i, start_j=j, moves=moves, n_moves=k,
                       truncated=jnp.logical_not(done))


def run_batched(spec: T.DPKernelSpec, result: T.DPResult,
                max_len: int | None = None) -> T.Alignment:
    """Batched traceback with early exit: ``result`` carries a leading
    batch axis (a vmapped fill); one ``while_loop`` advances every still-
    active row and terminates when the whole block has hit its END
    pointer — the loop runs max-path-length steps over the block, not
    ``max_len`` worst-case steps.  Bit-identical to ``run`` row by row.
    """
    tspec = spec.traceback
    assert tspec is not None, f"kernel {spec.name} has no traceback"
    if max_len is None:
        max_len = default_max_len(result.tb.shape[1:], result.tb_layout)
    n = result.end_i.shape[0]
    rows = jnp.arange(n)
    layout = result.tb_layout
    read = jax.vmap(lambda t, i, j: _make_reader(t, layout)(i, j))
    tb = result.tb

    def cond(c):
        i, j, state, k, done, moves = c
        return jnp.any(~done & (k < max_len))

    def body(c):
        i, j, state, k, done, moves = c
        active = ~done & (k < max_len)
        move, nstate, is_end, di, dj = _fsm_step(
            tspec, lambda a, b: read(tb, a, b), i, j, state)
        rec = jnp.where(is_end, jnp.uint8(0), move.astype(jnp.uint8))
        kc = jnp.clip(k, 0, max_len - 1)
        moves = moves.at[rows, kc].set(
            jnp.where(active, rec, moves[rows, kc]))
        i = jnp.where(active & ~is_end, i - di, i)
        j = jnp.where(active & ~is_end, j - dj, j)
        k = jnp.where(active & ~is_end, k + 1, k)
        state = jnp.where(active, nstate, state)
        done = done | (active & is_end)
        return (i, j, state, k, done, moves)

    init = (jnp.asarray(result.end_i, jnp.int32),
            jnp.asarray(result.end_j, jnp.int32),
            jnp.full((n,), tspec.initial_state, jnp.int32),
            jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), bool),
            jnp.zeros((n, max_len), jnp.uint8))
    i, j, _, k, done, moves = jax.lax.while_loop(cond, body, init)
    return T.Alignment(score=result.score, end_i=result.end_i,
                       end_j=result.end_j, start_i=i, start_j=j,
                       moves=moves, n_moves=k,
                       truncated=jnp.logical_not(done))


def raise_if_truncated(alignment: T.Alignment) -> T.Alignment:
    """Host-side guard: error out instead of consuming a corrupt partial
    path (call where device results land — batch harvest, SAM emission)."""
    t = alignment.truncated
    if t is not None and bool(np.any(np.asarray(t))):
        raise TracebackTruncated(
            "traceback ran out of its step budget before reaching a stop "
            "cell; the move array is a corrupt partial path (re-run with a "
            "larger max_len — the default budget derived from the pointer "
            "store is always sufficient)")
    return alignment


# ---------------------------------------------------------------------------
# Host-side utilities (not jitted)
# ---------------------------------------------------------------------------
_CIGAR_OPS = {T.MOVE_DIAG: "M", T.MOVE_UP: "D", T.MOVE_LEFT: "I"}


def block_cigars(moves, n_moves, ops=None, rows=None) -> list[str]:
    """A block's end->start move arrays -> every row's CIGAR (start->end).

    ``moves`` is ``[rows, max_len]`` and ``n_moves`` the ``[rows]`` path
    lengths; ``rows`` encodes only the first ``rows`` rows (the real rows
    of a padded block).  A row with no moves gives ``""``.  ``ops``
    overrides the move -> op-letter map.  The default follows the repo
    convention (MOVE_UP = query-consuming = 'D'); SAM emission with the
    read on the query axis passes ``{MOVE_DIAG: 'M', MOVE_UP: 'I',
    MOVE_LEFT: 'D'}`` instead (see ``repro.mapping.sam``).

    One device->host transfer and one run-length pass over the whole
    block: run starts, run lengths and row boundaries are array
    operations, and Python only spells each run and joins each row.
    """
    if ops is None:
        ops = _CIGAR_OPS
    n = np.asarray(n_moves, np.int64).reshape(-1)[:rows]
    if not n.size or n.max() <= 0:
        return [""] * n.size
    mv = np.asarray(moves)[: n.size, : int(n.max())]
    # a run starts at a path's first move and wherever the move changes
    start = np.empty(mv.shape, bool)
    start[:, 0] = True
    np.not_equal(mv[:, 1:], mv[:, :-1], out=start[:, 1:])
    start &= np.arange(mv.shape[1]) < n[:, None]
    row, k = np.nonzero(start)        # row-major: each row's runs end->start
    end = n[row]                      # a row's last run ends at its length
    same = np.flatnonzero(row[1:] == row[:-1])
    end[same] = k[same + 1]
    # reversed, the rows run last to first and each row's runs start->end
    runs = [f"{length}{ops[code]}" for length, code in
            zip((end - k)[::-1].tolist(), mv[row, k][::-1].tolist())]
    bounds = np.cumsum(np.bincount(row, minlength=n.size)[::-1]).tolist()
    return ["".join(runs[a:b]) for a, b in
            zip([0] + bounds[:-1], bounds)][::-1]


def moves_to_cigar(moves, n_moves, ops=None) -> str:
    """One end->start move array -> its CIGAR string (start->end order):
    the one-row case of :func:`block_cigars`, with the same ``ops``."""
    return block_cigars(np.asarray(moves)[None], np.asarray(n_moves),
                        ops=ops)[0]


def path_cells(alignment: T.Alignment):
    """The (i, j) cells on the path from start to end (host-side)."""
    i0, j0 = int(alignment.start_i), int(alignment.start_j)
    mv = np.asarray(alignment.moves)[: int(alignment.n_moves)][::-1]
    mv = mv.astype(np.int64)
    di = np.cumsum((mv == T.MOVE_DIAG) | (mv == T.MOVE_UP))
    dj = np.cumsum((mv == T.MOVE_DIAG) | (mv == T.MOVE_LEFT))
    ii = np.concatenate([[i0], i0 + di])
    jj = np.concatenate([[j0], j0 + dj])
    return [(int(a), int(b)) for a, b in zip(ii, jj)]
