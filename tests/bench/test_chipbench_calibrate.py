"""The calibration kernel computes what it claims (interpret mode)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import calibrate  # noqa: E402


def test_kernel_runs_the_stated_chains():
    x = calibrate.inputs(4)
    out = calibrate.make(16, 4, unroll=8, interpret=True)(x)
    assert np.array_equal(np.asarray(out),
                          calibrate.expected(np.asarray(x), 16, 4))
    with pytest.raises(ValueError):
        calibrate.make(12, 4, unroll=8)
