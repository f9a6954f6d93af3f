"""The one traffic generator: a mix file's parameters in, sequence pairs
and a schedule out.

A mix (``bench/traffic/<name>.json``) fixes the shapes of its requests
with its own ``shape_seed``: the length of every pair, their order and,
in an open loop, every gap between arrivals.  A run's ``--seed`` draws
the bases and the sequencing errors, so every seed does the same work in
the same order.

Pairs are a reference window and a read sampled from it with errors:

* ``reads.length``: ``{"uniform": [lo, hi]}`` or ``{"lognormal":
  {"n50": N, "sigma": s}, "min": lo}`` (reads under ``lo`` are dropped;
  for a log-normal, N50 = exp(mu + sigma^2));
* ``reads.piece`` (optional): a read longer than this is sent as
  consecutive pieces of this length and a last, shorter one, as a client
  that tiles long reads does;
* ``reads.window_extra``: ``[lo, hi]`` bases of reference window beyond
  the read, uniform;
* ``reads.divergence`` and ``reads.error_mix`` (shares of substitutions,
  insertions and deletions).

A closed loop keeps ``backlog`` requests outstanding.

A read keeps exactly its drawn length: errors are applied to the window
from a uniform start and the result is cut to length (a read that comes
out short continues into random bases).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Traffic:
    queries: List[np.ndarray]
    refs: List[np.ndarray]
    arrivals: Optional[np.ndarray]    # seconds from window start, or None

    def __len__(self):
        return len(self.queries)


def request_count(mix: dict, seconds: float) -> int:
    """Requests a run needs: the open loop's schedule over the window,
    or the closed loop's pool, which it cycles through."""
    if mix["loop"] == "open":
        return int(math.ceil(mix["rate_per_s"] * seconds))
    return int(mix["pool"])


def _lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        return rng.integers(lo, hi + 1, size=n)
    ln = spec["lognormal"]
    sigma = float(ln["sigma"])
    mu = math.log(float(ln["n50"])) - sigma ** 2
    lo = int(spec["min"])
    out = np.empty(0, np.int64)
    while len(out) < n:
        x = np.rint(rng.lognormal(mu, sigma, size=4 * n)).astype(np.int64)
        out = np.concatenate([out, x[x >= lo]])
    return out[:n]


def _pieces(lengths: np.ndarray, piece: int) -> np.ndarray:
    """Each length cut into pieces of ``piece`` and a shorter remainder."""
    full, rest = np.divmod(lengths, piece)
    out = [np.concatenate([np.full(f, piece), [r] if r else []])
           for f, r in zip(full, rest)]
    return np.concatenate(out).astype(np.int64)


def shapes(mix: dict, n: int):
    """``(read_len, window_len, gaps)`` of ``n`` requests, fixed by the
    mix alone."""
    rng = np.random.default_rng(int(mix["shape_seed"]))
    reads = mix["reads"]
    read_len = _lengths(reads["length"], rng, n)
    if "piece" in reads:
        read_len = _pieces(read_len, int(reads["piece"]))[:n]
    lo, hi = reads.get("window_extra", [0, 0])
    window = read_len + rng.integers(lo, hi + 1, size=n)
    gaps = None
    if mix["loop"] == "open":
        gaps = rng.exponential(1.0 / float(mix["rate_per_s"]), size=n)
    return read_len, window, gaps


def _mutate(src: np.ndarray, rng: np.random.Generator, rate: float,
            error_mix):
    """Sequencing errors on a concatenated base array; returns the
    output bases and, per source base, how many it emitted (0, 1, 2)."""
    sub, ins, dele = (float(x) for x in error_mix)
    u = rng.random(len(src))
    p_del = rate * dele
    p_ins = p_del + rate * ins
    p_sub = p_ins + rate * sub
    counts = np.where(u < p_del, 0, np.where(u < p_ins, 2, 1))
    base = np.where((u >= p_ins) & (u < p_sub),
                    (src + 1 + rng.integers(0, 3, len(src))) % 4, src)
    out = np.repeat(base.astype(np.uint8), counts)
    pos = np.cumsum(counts) - counts          # first output of each source
    ins_at = pos[counts == 2]
    out[ins_at] = rng.integers(0, 4, len(ins_at))
    return out, counts


def generate(mix: dict, seed: int, n: int) -> Traffic:
    """``n`` requests of ``mix`` for run seed ``seed``."""
    read_len, window, gaps = shapes(mix, n)
    rng = np.random.default_rng(seed)
    reads = mix["reads"]
    tail = 16 + int(0.1 * read_len.max())
    bases = rng.integers(0, 4, size=int(window.sum()), dtype=np.uint8)
    refs = np.split(bases, np.cumsum(window)[:-1])
    starts = rng.integers(0, window - read_len + 1)
    extra = rng.integers(0, 4, size=(n, tail), dtype=np.uint8)
    src = [np.concatenate([w[s:], e]) for w, s, e in zip(refs, starts, extra)]
    src_len = np.array([len(s) for s in src])
    out, counts = _mutate(np.concatenate(src), rng, float(reads["divergence"]),
                          reads["error_mix"])
    emitted = np.add.reduceat(counts, np.cumsum(src_len) - src_len)
    offs = np.cumsum(emitted) - emitted
    queries = []
    for k in range(n):
        q = out[offs[k]:offs[k] + min(read_len[k], emitted[k])]
        if len(q) < read_len[k]:
            q = np.concatenate([q, rng.integers(0, 4, read_len[k] - len(q),
                                                dtype=np.uint8)])
        queries.append(q)
    arrivals = None if gaps is None else np.cumsum(gaps)
    return Traffic(queries=queries, refs=list(refs), arrivals=arrivals)
