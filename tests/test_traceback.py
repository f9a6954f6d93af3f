"""moves_to_cigar coverage beyond the all-match case: I/D run-length
encoding, leading/trailing gaps, empty alignments, and op-map overrides;
block_cigars against it row by row."""
from __future__ import annotations

import re

import numpy as np
import pytest

from repro.core import types as T
from repro.core.traceback import block_cigars, moves_to_cigar

_CODE = {"M": T.MOVE_DIAG, "D": T.MOVE_UP, "I": T.MOVE_LEFT}


def enc(forward_ops: str):
    """Forward op string -> (end->start move array with slack, n_moves)."""
    mv = [_CODE[o] for o in forward_ops][::-1]
    arr = np.zeros((len(mv) + 4,), np.uint8)   # trailing junk must be ignored
    arr[: len(mv)] = mv
    arr[len(mv):] = _CODE["M"]
    return arr, len(mv)


def test_all_match():
    assert moves_to_cigar(*enc("MMMM")) == "4M"


def test_insertion_and_deletion_runs():
    assert moves_to_cigar(*enc("MMIIIMDD")) == "2M3I1M2D"
    assert moves_to_cigar(*enc("MDMDMD")) == "1M1D1M1D1M1D"


def test_leading_and_trailing_gaps():
    assert moves_to_cigar(*enc("DDMMI")) == "2D2M1I"
    assert moves_to_cigar(*enc("IMMMDD")) == "1I3M2D"


def test_empty_alignment():
    assert moves_to_cigar(np.zeros((6,), np.uint8), 0) == ""


def test_n_moves_truncates_trailing_junk():
    arr, n = enc("MMDD")
    assert moves_to_cigar(arr, n) == "2M2D"
    assert moves_to_cigar(arr, 2) != moves_to_cigar(arr, n)


def test_ops_override_swaps_sam_convention():
    sam_ops = {T.MOVE_DIAG: "M", T.MOVE_UP: "I", T.MOVE_LEFT: "D"}
    arr, n = enc("MMIIIMDD")       # default: I = MOVE_LEFT, D = MOVE_UP
    assert moves_to_cigar(arr, n, ops=sam_ops) == "2M3D1M2I"


def _random_block(rng, rows=9, width=40, n=None):
    """``[rows, width]`` moves of random ops and lengths (or ``n``), with
    junk (every code, the stop code too) past each row's ``n_moves``."""
    ops = (T.MOVE_DIAG, T.MOVE_UP, T.MOVE_LEFT)
    moves = rng.integers(0, 4, (rows, width)).astype(np.uint8)
    if n is None:
        n = rng.integers(0, width + 1, rows)
    for r in range(rows):
        moves[r, : n[r]] = rng.choice(ops, n[r])
    return moves, n


def _block_case(name, rng):
    """(moves, n_moves, ops, rows) for one ``block_cigars`` case."""
    if name == "random_ops":
        return (*_random_block(rng), None, None)
    if name == "ragged_rows":
        n = np.array([0, 7, 33, 40, 2, 1, 19, 40, 11])  # empty, full, one
        return (*_random_block(rng, n=n), None, None)
    if name == "single_run_and_alternating":
        moves, n = _random_block(rng, rows=4, width=12)
        moves[0], n[0] = T.MOVE_UP, 12              # one run, all the row
        moves[1, :12] = [T.MOVE_DIAG, T.MOVE_LEFT] * 6
        moves[2, :12] = [T.MOVE_UP, T.MOVE_DIAG, T.MOVE_LEFT] * 4
        n[1] = n[2] = 12
        return moves, n, None, None
    if name == "sam_ops":
        return (*_random_block(rng),
                {T.MOVE_DIAG: "M", T.MOVE_UP: "I", T.MOVE_LEFT: "D"}, None)
    assert name == "pad_rows_cut"
    moves, n = _random_block(rng)
    moves[6:] = 0                                   # pad rows: stop code
    return moves, n, None, 6


@pytest.mark.parametrize("case", ["random_ops", "ragged_rows",
                                  "single_run_and_alternating", "sam_ops",
                                  "pad_rows_cut"])
def test_block_cigars_match_row_by_row(rng, case):
    moves, n, ops, rows = _block_case(case, rng)
    got = block_cigars(moves, n, ops=ops, rows=rows)
    keep = len(n) if rows is None else rows
    assert got == [moves_to_cigar(moves[r], n[r], ops=ops)
                   for r in range(keep)]
    # independent of the encoder: each CIGAR spells its row's path
    # start->end in maximal runs
    letter = ops or {T.MOVE_DIAG: "M", T.MOVE_UP: "D", T.MOVE_LEFT: "I"}
    for r, cigar in enumerate(got):
        runs = re.findall(r"(\d+)([MID])", cigar)
        assert "".join(k + o for k, o in runs) == cigar
        assert "".join(int(k) * o for k, o in runs) == "".join(
            letter[int(m)] for m in moves[r, : n[r]][::-1])
        assert all(a[1] != b[1] for a, b in zip(runs, runs[1:]))


def test_block_cigars_empty_and_all_zero_blocks():
    moves = np.ones((3, 5), np.uint8)
    assert block_cigars(moves, np.zeros(3, np.int32)) == ["", "", ""]
    assert block_cigars(moves, np.array([2, 4, 1]), rows=0) == []
    assert block_cigars(np.ones((1, 7), np.uint8), [7]) == ["7M"]


def test_pack_lanes_roundtrip(rng):
    """pack_words slots decode back to the original pointers, including
    a ragged lane count (zero-padded tail)."""
    import jax.numpy as jnp
    from repro.core.traceback import pack_words, word_slot
    for pack in (1, 2, 4, 8):
        width = 8 // pack
        per_word = 32 // width
        lanes = 13                      # not a multiple of any word width
        ptr = rng.integers(0, 1 << width, lanes).astype(np.uint8)
        packed = np.asarray(pack_words(jnp.asarray(ptr), pack))
        nw = -(-lanes // per_word)
        assert packed.shape == (nw,) and packed.dtype == np.int32
        for i in range(lanes):
            got = int(np.asarray(word_slot(jnp.asarray(packed[i % nw]),
                                           i // nw, pack)))
            assert got == int(ptr[i]), (pack, i)


def test_truncated_traceback_raises_at_harvest(rng):
    """A max_len too small for the path must flag truncation, and the
    host-side guard must refuse the corrupt partial path."""
    import jax.numpy as jnp
    from repro.core import align, kernels_zoo
    from repro.core import traceback as tb_mod
    from repro.core.api import fill
    spec, params = kernels_zoo.make("global_linear")
    q = jnp.asarray(rng.integers(0, 4, 24).astype(np.uint8))
    res = fill(spec, params, q, q)
    full = tb_mod.run(spec, res)              # default budget: always safe
    assert not bool(np.asarray(full.truncated))
    assert int(full.n_moves) == 24
    short = tb_mod.run(spec, res, max_len=5)  # path needs 24 moves
    assert bool(np.asarray(short.truncated))
    with pytest.raises(tb_mod.TracebackTruncated):
        tb_mod.raise_if_truncated(short)
    tb_mod.raise_if_truncated(full)           # no-op on complete paths
    # the aligned paths produced by the plans are never truncated
    a = align(spec, params, q, q)
    assert not bool(np.asarray(a.truncated))


def test_path_cells_matches_moves(rng):
    from repro.core import align, kernels_zoo
    from repro.core.traceback import path_cells
    import jax.numpy as jnp
    spec, params = kernels_zoo.make("global_linear")
    q = jnp.asarray(rng.integers(0, 4, 17).astype(np.uint8))
    r = jnp.asarray(rng.integers(0, 4, 23).astype(np.uint8))
    a = align(spec, params, q, r)
    cells = path_cells(a)
    assert cells[0] == (int(a.start_i), int(a.start_j)) == (0, 0)
    assert cells[-1] == (int(a.end_i), int(a.end_j)) == (17, 23)
    # each step consumes at least one character on some axis
    for (i0, j0), (i1, j1) in zip(cells, cells[1:]):
        assert (i1 - i0, j1 - j0) in {(1, 1), (1, 0), (0, 1)}


def test_real_alignment_cigar_consumes_both_sequences(rng):
    """A global alignment's CIGAR must consume exactly q_len on the query
    axis (M+D under the repo convention) and r_len on the reference axis
    (M+I)."""
    from repro.core import align, kernels_zoo
    import jax.numpy as jnp
    spec, params = kernels_zoo.make("global_linear")
    q = jnp.asarray(rng.integers(0, 4, 21).astype(np.uint8))
    r = jnp.asarray(rng.integers(0, 4, 33).astype(np.uint8))
    a = align(spec, params, q, r)
    cigar = moves_to_cigar(np.asarray(a.moves), int(a.n_moves))
    q_span = sum(int(c) for c, o in re.findall(r"(\d+)([MDI])", cigar)
                 if o in "MD")
    r_span = sum(int(c) for c, o in re.findall(r"(\d+)([MDI])", cigar)
                 if o in "MI")
    assert (q_span, r_span) == (21, 33)
