"""bench_fill gates: quick parity in tier-1, full GCUPS sweep as slow."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import bench_fill  # noqa: E402


def test_quick_parity_and_memory_headline():
    """--quick mode: bit-identity asserts + the >= 2x in-flight batch
    claim (the GCUPS cells are reported, not asserted, in quick mode)."""
    metrics = bench_fill.run(quick=True)
    assert metrics["cells"], "no timed cells"
    assert metrics["mem"]["global_linear"]["batch_ratio"] >= 4.0
    assert metrics["mem"]["global_affine"]["batch_ratio"] >= 2.0


@pytest.mark.slow
def test_full_gcups_sweep_meets_targets():
    """Full engine x bucket x batch sweep: every optimized schedule is
    bit-identical to the unpacked K=1 seed (``bench_fill.run`` asserts
    it cell by cell).  Its CPU wall-clock speed-ups are reported, not
    gated: a speed claim needs a chip measurement."""
    metrics = bench_fill.run(quick=False)
    assert metrics["cells"], "no timed cells"
    assert all(c["speedup"] > 0 for c in metrics["cells"])
