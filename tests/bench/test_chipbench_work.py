"""Work counts and the peaks table (bench/work.py)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import work  # noqa: E402


def test_ops_per_cell_follow_the_recurrence():
    # I: 2 add, 1 max, 1 compare; D the same; M: compare, select, add;
    # H and source: 2 compare, 2 select, 2 max; pointer: 2 shift, 2 or
    assert work.ops_per_cell("global_affine") == 4 + 4 + 3 + 6 + 4
    # local adds the zero floor: compare, select, max
    assert work.ops_per_cell("local_affine") == 21 + 3


def test_hand_counted_requests():
    assert work.request_ops("global_affine", 3, 4) == 21 * 12
    assert work.request_ops("local_affine", 100, 150) == 24 * 15000
    # 3 + 4 bases in, two int32 lengths, score and end cell, 4 moves and
    # their count out
    assert work.request_bytes(3, 4) == 7 + 8 + 12 + 4 + 4


def test_unknown_kernel_and_device_raise():
    with pytest.raises(KeyError):
        work.ops_per_cell("profile")
    with pytest.raises(KeyError):
        work.peaks("cpu")
    with pytest.raises(KeyError):
        work.roofline("global_affine", [(3, 4)], 1.0, "TPU v9 imaginary")


def test_roofline_names_its_bound(monkeypatch, tmp_path):
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"test chip": {
        "int32_ops_per_s": 1e9, "int32_ops_source": "test",
        "hbm_bytes_per_s": 1e7, "hbm_source": "test"}}))
    monkeypatch.setattr(work, "PEAKS_FILE", table)
    # 100 x 100 cells: 210,000 ops (2.1e-4 s) against 324 bytes (3.2e-5 s)
    r = work.roofline("global_affine", [(100, 100)], 1.0, "test chip")
    assert r["bound"] == "ops" and r["share"] == pytest.approx(
        21 * 100 * 100 / 1e9)
    # one cell: 21 ops (2.1e-8 s) against 27 bytes (2.7e-6 s)
    r = work.roofline("global_affine", [(1, 1)], 1.0, "test chip")
    assert r["bound"] == "bytes" and r["bytes"] == 27
