"""The traffic generator (bench/traffic.py)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, traffic  # noqa: E402

BIG = 2**33 + 12345           # seeds beyond 32 bits


def test_same_seed_same_inputs_and_every_seed_the_same_shapes():
    mx = harness.mix("illumina_poisson")
    a = traffic.generate(mx, BIG, 500)
    b = traffic.generate(mx, BIG, 500)
    c = traffic.generate(mx, BIG + 1, 500)
    assert all(np.array_equal(x, y) for x, y in zip(a.queries, b.queries))
    assert np.array_equal(a.arrivals, b.arrivals)
    lens = lambda t: sorted((len(q), len(r)) for q, r in  # noqa: E731
                            zip(t.queries, t.refs))
    assert lens(a) == lens(c)
    gaps = lambda t: np.sort(np.diff(t.arrivals, prepend=0.0))  # noqa: E731
    assert np.allclose(gaps(a), gaps(c))
    assert not all(np.array_equal(x, y) for x, y in zip(a.queries,
                                                         c.queries))


def test_reads_keep_their_lengths_and_divergence():
    mx = harness.mix("ont_closed")
    t = traffic.generate(mx, BIG, 64)
    rl, _, _ = traffic.shapes(mx, 64)
    assert sorted(len(q) for q in t.queries) == sorted(rl.tolist())
    assert all(1 <= len(q) <= 16384 and len(q) == len(r)
               for q, r in zip(t.queries, t.refs))
    assert max(len(q) for q in t.queries) == 16384
    short = traffic.generate(harness.mix("illumina_closed"), BIG, 256)
    for q, r in zip(short.queries, short.refs):
        assert 100 <= len(q) <= 150 and len(q) <= len(r) <= len(q) + 100
        assert q.dtype == np.uint8 and q.max() < 4
