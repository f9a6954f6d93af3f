"""Plain Gotoh alignment in NumPy: the yardstick that decides ``correct``.

Independent of the system under test: it imports nothing of it and takes
nothing it made.  Scoring follows the configuration files' convention
(a gap of length ``k`` scores ``gap_open + (k - 1) * gap_extend``, every
value negative but ``match``), with three states per cell:

* ``H`` best score ending at ``(i, j)``;
* ``D`` a gap that consumes the query (a vertical move, CIGAR ``D``);
* ``I`` a gap that consumes the reference (a horizontal move, CIGAR ``I``).

A row is computed in a handful of vector operations over a batch of
pairs.  The horizontal gap state is a prefix maximum: opening a gap from
a cell that is itself in a gap never beats extending it, because
``gap_open <= gap_extend``, so

    I[i, j] = gap_open + (j - 1) * gap_extend
              + max_{k < j} (T[i, k] - k * gap_extend)

where ``T`` is the best of the diagonal and vertical candidates (and 0
for local alignment).

``bits`` < 32 saturates every stored value to that signed width, as an
8-bit SIMD first pass would: the benchmark's control, which must be
judged not correct.
"""
from __future__ import annotations

import multiprocessing
import os
import re
from concurrent.futures import ProcessPoolExecutor
from typing import List, Optional, Sequence, Tuple

import numpy as np

DEAD = -(1 << 28)
PROCESS_MIN_CELLS = 1 << 28   # less work runs in the calling process
_CIGAR = re.compile(r"(\d+)([MDI])")


def _clip(x, bits):
    if bits >= 32:
        return x
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    return np.clip(x, lo, hi)


def best_alignments(queries: Sequence[np.ndarray], refs: Sequence[np.ndarray],
                    scoring: dict, local: bool, *,
                    ends: Optional[Sequence[Tuple[int, int]]] = None,
                    bits: int = 32, workers: int = 8) -> List[dict]:
    """Optimal score and end cell of each pair.

    Global alignment ends at ``(len(q), len(r))``; local alignment at the
    first best cell in row-major order.  With ``ends``, each result also
    carries ``H_at_end``: the best score of a path ending at that cell,
    so a reported end cell can be checked for optimality when several
    cells tie.  Batches of similar lengths, about ``1 << 14`` cells of a
    row each, largest first, run on up to ``workers`` processes once the
    work is large enough to pay for starting them (threads would not
    help: the interpreter lock serialises the many small row operations).
    """
    n = len(queries)
    out: List[Optional[dict]] = [None] * n
    order = sorted(range(n), key=lambda k: (-len(refs[k]), -len(queries[k])))
    chunks, s = [], 0
    while s < n:
        batch = max(1, (1 << 14) // (len(refs[order[s]]) + 1))
        chunks.append(order[s:s + batch])
        s += batch
    jobs = [([queries[k] for k in idx], [refs[k] for k in idx], scoring,
             local, None if ends is None else [ends[k] for k in idx], bits)
            for idx in chunks]
    cells = sum(len(q) * len(r) for q, r in zip(queries, refs))
    procs = max(1, min(workers, len(chunks), os.cpu_count() or 1))
    if procs == 1 or cells < PROCESS_MIN_CELLS:
        results = [_batch(*job) for job in jobs]
    else:
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(procs, mp_context=ctx) as pool:
            results = list(pool.map(_batch, *zip(*jobs)))
    for idx, res in zip(chunks, results):
        for k, r in zip(idx, res):
            out[k] = r
    return out


def _batch(qs, rs, sc, local, ends, bits):
    b = len(qs)
    ql = np.array([len(q) for q in qs], np.int64)
    rl = np.array([len(r) for r in rs], np.int64)
    Q, R = int(ql.max()), int(rl.max())
    qm = np.full((b, Q), 255, np.uint8)
    rm = np.full((b, R), 254, np.uint8)
    for k in range(b):
        qm[k, :ql[k]] = qs[k]
        rm[k, :rl[k]] = rs[k]
    match, mismatch = int(sc["match"]), int(sc["mismatch"])
    go, ge = int(sc["gap_open"]), int(sc["gap_extend"])
    j = np.arange(R + 1, dtype=np.int32)
    gap_j = np.where(j == 0, 0, go + (j - 1) * ge)      # row 0 / column 0
    col_ok = j[None, :] <= rl[:, None]
    rows = np.arange(b)

    if local:
        H = np.zeros((b, R + 1), np.int32)
    else:
        H = np.broadcast_to(gap_j, (b, R + 1)).astype(np.int32)
    H = _clip(H, bits)
    D = np.full((b, R + 1), DEAD, np.int32)
    best = np.full(b, DEAD, np.int64)
    bi = np.zeros(b, np.int64)
    bj = np.zeros(b, np.int64)
    if local:                       # row 0 holds only zeros: (0, 0) first
        best[:] = 0
    at_end = np.full(b, DEAD, np.int64)
    if ends is not None:
        ei = np.array([e[0] for e in ends], np.int64)
        ej = np.clip(np.array([e[1] for e in ends], np.int64), 0, R)
        hit = ei == 0
        at_end[hit] = H[rows[hit], ej[hit]]
    if not local:
        done = ql == 0
        best[done] = H[rows[done], rl[done]]
        bi[done], bj[done] = 0, rl[done]
    ramp = j * ge
    for i in range(1, Q + 1):
        sub = np.where(qm[:, i - 1:i] == rm, np.int32(match),
                       np.int32(mismatch))
        D = _clip(np.maximum(H + go, D + ge), bits)
        T = np.empty_like(H)
        T[:, 1:] = np.maximum(H[:, :-1] + sub, D[:, 1:])
        T[:, 0] = 0 if local else go + (i - 1) * ge
        if local:
            T = np.maximum(T, 0)
        T = _clip(T, bits)
        P = np.maximum.accumulate(T - ramp, axis=1)
        I = np.full_like(H, DEAD)
        I[:, 1:] = go + ramp[1:] - ge + P[:, :-1]      # go + (j-1)ge + P[j-1]
        I = _clip(I, bits)
        H = np.maximum(T, I)
        if not local:
            D[:, 0] = T[:, 0]
        live = i <= ql
        if local:
            Hm = np.where(col_ok, H, DEAD)
            jm = Hm.argmax(axis=1)
            vm = Hm[rows, jm]
            up = live & (vm > best)
            best[up], bi[up], bj[up] = vm[up], i, jm[up]
        else:
            done = ql == i
            best[done] = H[rows[done], rl[done]]
            bi[done], bj[done] = i, rl[done]
        if ends is not None:
            hit = ei == i
            at_end[hit] = H[rows[hit], ej[hit]]
    res = []
    for k in range(b):
        r = {"score": int(best[k]), "end": (int(bi[k]), int(bj[k]))}
        if ends is not None:
            r["H_at_end"] = int(at_end[k])
        res.append(r)
    return res


def rescore_cigar(query: np.ndarray, ref: np.ndarray, scoring: dict,
                  cigar: str, end: Tuple[int, int]):
    """Score of the path a CIGAR describes, ending at ``end``, and its
    start cell; ``None`` where the path leaves the matrix.

    CIGAR letters follow the service's convention: ``M`` consumes one
    query and one reference base, ``D`` one query base, ``I`` one
    reference base.
    """
    runs = [(int(n), op) for n, op in _CIGAR.findall(cigar)]
    if "".join(f"{n}{op}" for n, op in runs) != cigar:
        return None
    di = sum(n for n, op in runs if op in "MD")
    dj = sum(n for n, op in runs if op in "MI")
    ei, ej = int(end[0]), int(end[1])
    i, j = ei - di, ej - dj
    if i < 0 or j < 0 or ei > len(query) or ej > len(ref):
        return None
    start = (i, j)
    match, mismatch = int(scoring["match"]), int(scoring["mismatch"])
    go, ge = int(scoring["gap_open"]), int(scoring["gap_extend"])
    total = 0
    for n, op in runs:
        if op == "M":
            same = np.asarray(query[i:i + n]) == np.asarray(ref[j:j + n])
            total += int(same.sum()) * match + int(n - same.sum()) * mismatch
            i, j = i + n, j + n
        elif op == "D":
            total += go + (n - 1) * ge
            i += n
        else:
            total += go + (n - 1) * ge
            j += n
    return total, start


def judge(requests, results, scoring: dict, local: bool) -> dict:
    """Compare served results with the reference.

    ``requests`` are ``(query, ref)`` pairs and ``results`` the answers
    to judge (``score``, ``end`` and, where there is one, ``cigar``).
    Returns the counts of pairs whose score or end cell is wrong and of
    pairs whose path is wrong.  A local end cell is right when the best
    path ending there scores the optimum; a global one is the corner.
    """
    ref = best_alignments([q for q, _ in requests], [r for _, r in requests],
                          scoring, local,
                          ends=[tuple(res["end"]) for res in results])
    wrong_score = wrong_path = 0
    for (q, r), want, got in zip(requests, ref, results):
        end = tuple(int(x) for x in got["end"])
        if local:
            end_ok = (0 <= end[0] <= len(q) and 0 <= end[1] <= len(r)
                      and want["H_at_end"] == want["score"])
        else:
            end_ok = end == (len(q), len(r))
        if float(got["score"]) != want["score"] or not end_ok:
            wrong_score += 1
        if got.get("cigar") is None:
            continue
        path = rescore_cigar(q, r, scoring, got["cigar"], end)
        if path is None or path[0] != want["score"] or (
                not local and path[1] != (0, 0)):
            wrong_path += 1
    return {"score_or_end_wrong": wrong_score, "path_wrong": wrong_path}


def control_answers(requests, scoring: dict, local: bool,
                    bits: int = 8) -> List[dict]:
    """The control: this reference with every value saturated to
    ``bits``, put where the service's answers would be.  It has no path,
    so it can only fail the score-and-end comparison."""
    return best_alignments([q for q, _ in requests], [r for _, r in requests],
                           scoring, local, bits=bits)
