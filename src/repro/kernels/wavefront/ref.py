"""Pure-jnp oracle for the Pallas wavefront kernel.

The kernel must produce, for a given DPKernelSpec:
  * per-(chunk, lane) running-best score and its column, over the spec's
    objective region, and
  * the chunk-local coalesced traceback store (lane = row within chunk,
    w = chunk-local wavefront = lane + j - 1), ``per_word`` wavefronts
    packed per int32 word: tb[chunk, w // per_word, lane] holds the
    pointer in slot w % per_word (``traceback.word_layout``).

This oracle derives all three from the reference engine's full matrix.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import reference
from repro.core.spec_utils import region_mask
from repro.core.traceback import word_layout
from repro.kernels.wavefront.kernel import fill_geometry


def run(spec, params, query, ref, q_len=None, r_len=None, n_pe: int = 8,
        tb_pack: int = 1):
    Q, R = query.shape[0], ref.shape[0]
    assert Q % n_pe == 0, "oracle expects padded query"
    q_len = Q if q_len is None else int(q_len)
    r_len = R if r_len is None else int(r_len)
    scores, tb = reference.fill_matrix(spec, params, query, ref, q_len, r_len)
    scores = np.asarray(scores)
    tb = np.asarray(tb)
    n_chunks = Q // n_pe
    width, per_word = word_layout(tb_pack)
    n_groups, _ = fill_geometry(n_pe, R, tb_pack)

    tb_out = np.zeros((n_chunks, n_groups, n_pe), np.int64)
    best = np.full((n_chunks, n_pe), float(np.asarray(spec.sentinel())))
    best_j = np.zeros((n_chunks, n_pe), np.int32)
    ii = np.arange(Q + 1)[:, None]
    jj = np.arange(R + 1)[None, :]
    rmask = np.asarray(region_mask(spec, jnp.asarray(ii), jnp.asarray(jj),
                                   q_len, r_len))
    prim = scores[:, :, spec.primary_layer]
    for c in range(n_chunks):
        for l in range(n_pe):
            i = c * n_pe + l + 1  # global DP row
            if i > Q:
                continue
            for j in range(1, R + 1):
                w = l + j - 1
                g, slot = divmod(w, per_word)
                tb_out[c, g, l] |= (int(tb[i, j]) & ((1 << width) - 1)) \
                    << (slot * width)
                if rmask[i, j]:
                    v = prim[i, j]
                    if (v < best[c, l]) if spec.is_min else (v > best[c, l]):
                        best[c, l] = v
                        best_j[c, l] = j
    # int32 words: reinterpret the unsigned packing's top bit as sign
    tb_out = tb_out.astype(np.uint32).view(np.int32)
    return best.astype(np.asarray(scores).dtype), best_j, tb_out
