"""Minimizer reference index (mapping stage 1).

A minimap2-style (k, w) minimizer sketch built with jnp ops so both index
construction and lookup jit: k-mers pack into 2-bit codes, run through a
murmur3-style integer mixer, and each w-window keeps its minimum-hash
k-mer.  The index itself is a sorted bucket table — minimizer hashes
sorted with their reference positions — so lookup is two ``searchsorted``
calls returning a contiguous [lo, hi) occurrence range per query hash.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

MAX_KMER = 16   # 2 bits/base in a uint32

# k-mers containing ambiguous codes (N = 4) hash to this sentinel: it is
# the uint32 maximum, so window-minimum selection avoids it, and
# build_index drops it from the table, so lookups of all-ambiguous
# windows find nothing.  (A real k-mer hashing here is dropped too —
# a 1-in-4-billion false negative.)
AMBIG_HASH = np.uint32(0xFFFFFFFF)


def mix32(h):
    """murmur3 fmix32 finalizer — an invertible avalanche over uint32."""
    h = jnp.asarray(h, jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def kmer_hashes(seq, k: int):
    """(L,) uint8 codes -> (L-k+1,) uint32 mixed hashes of packed k-mers."""
    if k > MAX_KMER:
        raise ValueError(f"k={k} exceeds {MAX_KMER} (2-bit packing)")
    seq = jnp.asarray(seq, jnp.uint32)
    n = seq.shape[0] - k + 1
    # k shifted slices, not an (n, k) gather: the TPU compiler takes
    # minutes over a gather that size at a 1 Mb reference
    packed = jnp.zeros((n,), jnp.uint32)
    unambig = jnp.ones((n,), bool)
    for t in range(k):
        codes = seq[t:t + n]
        packed = (packed << 2) | (codes & 3)
        unambig = unambig & (codes < 4)
    return jnp.where(unambig, mix32(packed), jnp.uint32(AMBIG_HASH))


def minimizers(seq, k: int, w: int):
    """Per-window minimizers: ``(pos, hash)`` arrays of length L-k-w+2.

    Window t covers k-mer starts [t, t+w); ``pos[t]`` is the (leftmost)
    position of the minimum hash in that window.  Consecutive windows
    usually repeat a minimizer — callers dedupe by position.
    """
    h = kmer_hashes(seq, k)
    n_win = h.shape[0] - w + 1
    # running minimum over w shifted slices; strict < keeps the leftmost
    val = h[:n_win]
    arg = jnp.zeros((n_win,), jnp.int32)
    for t in range(1, w):
        cand = h[t:t + n_win]
        upd = cand < val
        val = jnp.where(upd, cand, val)
        arg = jnp.where(upd, jnp.int32(t), arg)
    pos = jnp.arange(n_win, dtype=jnp.int32) + arg
    return pos, val


@dataclasses.dataclass(frozen=True)
class MinimizerIndex:
    """Sorted bucket table over one reference sequence.

    ``hashes`` is sorted ascending; ``positions[i]`` is the reference
    start of the k-mer behind ``hashes[i]``.  Registered as a pytree so
    the whole index passes straight into jitted seed/chain functions.
    """
    k: int
    w: int
    ref_len: int
    hashes: jnp.ndarray      # (M,) uint32, sorted
    positions: jnp.ndarray   # (M,) int32

    @property
    def n_minimizers(self) -> int:
        return int(self.hashes.shape[0])


jax.tree_util.register_dataclass(
    MinimizerIndex, data_fields=["hashes", "positions"],
    meta_fields=["k", "w", "ref_len"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _sketch(ref, k, w):
    return minimizers(ref, k, w)


def build_index(ref, k: int = 13, w: int = 8) -> MinimizerIndex:
    """Sketch ``ref`` and sort the minimizer table by hash."""
    ref = jnp.asarray(ref, jnp.uint8)
    if ref.shape[0] < k + w - 1:
        raise ValueError(f"reference ({ref.shape[0]}) shorter than k+w-1")
    pos, h = _sketch(ref, k, w)
    pos_np = np.asarray(pos)
    h_np = np.asarray(h)
    # adjacent windows share minimizers; one entry per distinct position
    _, first = np.unique(pos_np, return_index=True)
    pos_np, h_np = pos_np[first], h_np[first]
    # drop ambiguous (N-containing) minimizers from the table
    keep = h_np != AMBIG_HASH
    pos_np, h_np = pos_np[keep], h_np[keep]
    order = np.lexsort((pos_np, h_np))
    return MinimizerIndex(k=k, w=w, ref_len=int(ref.shape[0]),
                          hashes=jnp.asarray(h_np[order]),
                          positions=jnp.asarray(pos_np[order]))


def lookup_range(index: MinimizerIndex, query_hashes):
    """[lo, hi) occurrence range in the sorted table per query hash."""
    lo = jnp.searchsorted(index.hashes, query_hashes, side="left")
    hi = jnp.searchsorted(index.hashes, query_hashes, side="right")
    return lo, hi
