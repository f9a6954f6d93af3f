"""moves_to_cigar coverage beyond the all-match case: I/D run-length
encoding, leading/trailing gaps, empty alignments, and op-map overrides."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import types as T
from repro.core.traceback import moves_to_cigar

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
    import re
    q_span = sum(int(c) for c, o in re.findall(r"(\d+)([MDI])", cigar)
                 if o in "MD")
    r_span = sum(int(c) for c, o in re.findall(r"(\d+)([MDI])", cigar)
                 if o in "MI")
    assert (q_span, r_span) == (21, 33)
