"""The benchmark's plain reference and its control (bench/reference.py)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, reference, traffic  # noqa: E402

BWA = {"match": 1, "mismatch": -4, "gap_open": -7, "gap_extend": -1}
ONT = {"match": 2, "mismatch": -4, "gap_open": -6, "gap_extend": -2}


def gotoh_loops(q, r, sc, local):
    """Cell-by-cell Gotoh: the textbook loop the vectorised rows must
    equal."""
    neg = -(1 << 28)
    go, ge = sc["gap_open"], sc["gap_extend"]
    n, m = len(q), len(r)
    H = [[0] * (m + 1) for _ in range(n + 1)]
    D = [[neg] * (m + 1) for _ in range(n + 1)]
    I = [[neg] * (m + 1) for _ in range(n + 1)]  # noqa: E741
    for j in range(1, m + 1):
        H[0][j] = 0 if local else go + (j - 1) * ge
    for i in range(1, n + 1):
        H[i][0] = 0 if local else go + (i - 1) * ge
    best, end = (0, (0, 0)) if local else (None, (n, m))
    for i in range(1, n + 1):
        for j in range(1, m + 1):
            I[i][j] = max(H[i][j - 1] + go, I[i][j - 1] + ge)
            D[i][j] = max(H[i - 1][j] + go, D[i - 1][j] + ge)
            s = sc["match"] if q[i - 1] == r[j - 1] else sc["mismatch"]
            h = max(H[i - 1][j - 1] + s, D[i][j], I[i][j])
            H[i][j] = max(h, 0) if local else h
            if local and H[i][j] > best:
                best, end = H[i][j], (i, j)
    return (best if local else H[n][m]), end


@pytest.mark.parametrize("local", [False, True])
def test_rows_equal_the_textbook_loop(local):
    rng = np.random.default_rng(5)
    sc = ONT if local else BWA
    qs = [rng.integers(0, 4, int(rng.integers(1, 24))).astype(np.uint8)
          for _ in range(30)]
    rs = [rng.integers(0, 4, int(rng.integers(1, 30))).astype(np.uint8)
          for _ in range(30)]
    got = reference.best_alignments(qs, rs, sc, local)
    for q, r, g in zip(qs, rs, got):
        score, end = gotoh_loops(q, r, sc, local)
        assert (g["score"], g["end"]) == (score, end)


def test_worker_processes_give_what_the_calling_process_gives(monkeypatch):
    """Above ``PROCESS_MIN_CELLS`` the batches run on worker processes;
    the answers are the same, in the order asked."""
    rng = np.random.default_rng(8)
    qs = [rng.integers(0, 4, int(rng.integers(800, 1200))).astype(np.uint8)
          for _ in range(24)]
    rs = [rng.integers(0, 4, len(q) + 20).astype(np.uint8) for q in qs]
    ends = [(len(q), 7) for q in qs]
    alone = reference.best_alignments(qs, rs, ONT, True, ends=ends, workers=1)
    monkeypatch.setattr(reference, "PROCESS_MIN_CELLS", 0)
    used, pool = [], reference.ProcessPoolExecutor

    def spy(n, **kw):
        used.append(n)
        return pool(n, **kw)
    monkeypatch.setattr(reference, "ProcessPoolExecutor", spy)
    pooled = reference.best_alignments(qs, rs, ONT, True, ends=ends,
                                       workers=2)
    assert pooled == alone
    assert used == [2]


def test_hand_counted_pairs():
    acgt = np.array([0, 1, 2, 3], np.uint8)
    # four matches
    assert reference.best_alignments([acgt], [acgt], BWA, False)[0][
        "score"] == 4
    # one base deleted from the query: 3 matches and a gap of one, -7
    q = np.array([0, 1, 3], np.uint8)
    assert reference.best_alignments([q], [acgt], BWA, False)[0][
        "score"] == 3 - 7
    # local: the best part is "CGT" against "CGT", 3 * 2, ending at (4, 4)
    q = np.array([3, 1, 2, 3], np.uint8)
    res = reference.best_alignments([q], [acgt], ONT, True)[0]
    assert (res["score"], res["end"]) == (6, (4, 4))


def test_rescore_cigar():
    acgt = np.array([0, 1, 2, 3], np.uint8)
    q = np.array([0, 1, 3], np.uint8)
    assert reference.rescore_cigar(q, acgt, BWA, "2M1I1M", (3, 4)) == (
        3 - 7, (0, 0))
    assert reference.rescore_cigar(q, acgt, BWA, "9M", (3, 4)) is None
    assert reference.rescore_cigar(q, acgt, BWA, "2M1X", (3, 4)) is None


def test_judge_counts_wrong_scores_ends_and_paths():
    acgt = np.array([0, 1, 2, 3], np.uint8)
    q = np.array([0, 1, 3], np.uint8)
    good = {"score": -4.0, "end": (3, 4), "cigar": "2M1I1M"}
    assert reference.judge([(q, acgt)], [good], BWA, False) == {
        "score_or_end_wrong": 0, "path_wrong": 0}
    worse_path = dict(good, cigar="1M1I2M")      # a gap elsewhere: -9
    assert reference.judge([(q, acgt)], [worse_path], BWA, False)[
        "path_wrong"] == 1
    wrong = dict(good, score=-3.0)
    assert reference.judge([(q, acgt)], [wrong], BWA, False)[
        "score_or_end_wrong"] == 1


@pytest.mark.parametrize("mix,cfg,n", [("illumina_closed", "short_illumina",
                                         64),
                                        ("ont_closed", "long_ont", 2)])
def test_control_is_not_correct(mix, cfg, n):
    """The control, the reference saturated to 8 bits, fails the
    score comparison on the cell's own traffic; at 32 bits it passes."""
    mx, c = harness.mix(mix), harness.config(cfg)
    tr = traffic.generate(mx, 2**31 + 3, n)
    pairs = list(zip(tr.queries, tr.refs))
    local = c["kernel"].startswith("local")
    exact = reference.best_alignments(tr.queries, tr.refs, c["scoring"],
                                      local)
    assert reference.judge(pairs, exact, c["scoring"], local)[
        "score_or_end_wrong"] == 0
    ctl = reference.control_answers(pairs, c["scoring"], local, bits=8)
    assert reference.judge(pairs, ctl, c["scoring"], local)[
        "score_or_end_wrong"] > 0
