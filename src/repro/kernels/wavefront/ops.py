"""jit'd wrapper around the Pallas wavefront kernel: padding, launch, and
the cross-strip reduction (the paper's block-level reduction logic),
returning the same DPResult the pure-JAX engines produce.

The kernel is batched natively (the grid's first axis is the pair), and
``run`` is a single-pair engine whose ``vmap`` *is* that grid axis
(:func:`repro.kernels.grid_vmap.grid_vmap`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import types as T
from repro.core.engine import resolve_tb_pack
from repro.core.traceback import word_layout
from repro.kernels.grid_vmap import grid_vmap
from . import N_PE, VMEM_CAP_BYTES
from . import kernel as K

_MIB = 1 << 20
_VMEM_FLOOR = 16 * _MIB            # v5e's default scoped-VMEM limit


def _tile_bytes(rows: int, lanes: int, itemsize: int) -> int:
    """VMEM bytes of a ``(rows, lanes)`` 32-bit tile-padded block."""
    return -(-rows // 8) * 8 * -(-lanes // 128) * 128 * itemsize


def vmem_bytes(spec, q_bucket: int, r_bucket: int, params=None,
               n_pe: int = N_PE, tb_pack: Optional[int] = None) -> int:
    """Static VMEM footprint of the wavefront Pallas kernel at a bucket
    shape: every BlockSpec block (tile-padded, double-buffered by the
    pipeline) plus the preserved-row scratch.  Pure arithmetic over the
    shapes :func:`kernel.wavefront_fill` declares (no trace, no compile)
    — the plan linter's budget check and the kernel's own
    ``vmem_limit_bytes``."""
    pack = resolve_tb_pack(spec, tb_pack)
    if n_pe % pack:
        pack = 1
    R = max(int(r_bucket), 1)
    L = spec.n_layers
    sb = 4                                    # scores, chars: 32-bit lanes
    C = int(np.prod(spec.char_shape, dtype=np.int64))
    n_groups, top_rows = K.fill_geometry(n_pe, R, pack)
    W = n_groups * word_layout(pack)[1]
    blocks = (_tile_bytes(C, n_pe, 4)         # query strip
              + C * _tile_bytes(W, n_pe, 4)   # skewed reference stream
              + L * _tile_bytes(top_rows, n_pe, sb)   # preserved-row seed
              + _tile_bytes(L, n_pe, sb)      # strip's left boundary
              + _tile_bytes(1, n_pe, sb) + _tile_bytes(1, n_pe, 4))
    if spec.traceback is not None:
        blocks += _tile_bytes(n_groups, n_pe, 4)
    if params is not None:
        for leaf in jax.tree_util.tree_leaves(params):
            size = int(np.prod(jnp.shape(leaf), dtype=np.int64))
            if size > 1:                      # scalars live in SMEM
                blocks += size * jnp.dtype(jnp.result_type(leaf)).itemsize
    scratch = L * _tile_bytes(top_rows, n_pe, sb)
    return 2 * blocks + scratch


def vmem_limit(estimate: int) -> int:
    """The kernel's scoped-VMEM request: its estimate plus headroom for
    Mosaic's own temporaries, never below the default limit."""
    return int(min(max(estimate + 4 * _MIB, _VMEM_FLOOR), VMEM_CAP_BYTES))


def run(spec, params, query, ref, q_len=None, r_len=None,
        interpret: bool = False, n_pe: int = N_PE,
        tb_pack: Optional[int] = None) -> T.DPResult:
    Q, R = query.shape[0], ref.shape[0]
    q_len = jnp.asarray(Q if q_len is None else q_len, jnp.int32)
    r_len = jnp.asarray(R if r_len is None else r_len, jnp.int32)
    pack = resolve_tb_pack(spec, tb_pack)
    if n_pe % pack:
        pack = 1                    # lane strip must split evenly into words

    pad = (-Q) % n_pe
    if pad:
        query = jnp.concatenate(
            [query, jnp.zeros((pad,) + query.shape[1:], query.dtype)], axis=0)

    fill = grid_vmap(functools.partial(K.wavefront_fill, spec, n_pe=n_pe,
                                       interpret=interpret, tb_pack=pack))
    tb, best, best_j = fill(params, query, ref, jnp.stack([q_len, r_len]))
    layout = ("chunk", n_pe, pack)
    flat = best.reshape(-1)
    if spec.is_sum:
        # sum semiring: per-lane accumulators hold partial region mass;
        # the cross-strip reduction is the ⊕-fold (dead lanes underflow)
        return T.DPResult(score=spec.reduce_best(flat),
                          end_i=jnp.int32(0), end_j=jnp.int32(0),
                          tb=tb, tb_layout=layout)
    k = spec.arg_best(flat)
    score = flat[k]
    lane = k % n_pe
    chunk = k // n_pe
    end_i = (chunk * n_pe + lane + 1).astype(jnp.int32)
    end_j = best_j.reshape(-1)[k]
    return T.DPResult(score=score, end_i=end_i, end_j=end_j,
                      tb=tb, tb_layout=layout)
