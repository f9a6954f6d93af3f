"""Pallas wavefront kernel vs its pure-jnp oracle (kernels/wavefront/ref).

Per the assignment: sweep shapes/dtypes per kernel and assert_allclose
against the oracle, in interpret mode (CPU executes the kernel body).
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import align, kernels_zoo
from repro.kernels.wavefront import ops as wops
from repro.kernels.wavefront import ref as wref

from conftest import make_kernel_inputs

# kernels with distinct datapaths: linear, affine, two-piece, profile(f32),
# dtw(min/f32/complex), viterbi(no-tb), banded, sdtw(int32), protein(matrix)
SWEEP_KERNELS = [1, 2, 3, 4, 5, 7, 9, 10, 11, 14, 15]


@pytest.mark.parametrize("kid", SWEEP_KERNELS)
@pytest.mark.parametrize("n_pe,nq,nr", [(8, 32, 32), (16, 32, 24),
                                        (8, 24, 40)])
def test_kernel_matches_oracle(kid, n_pe, nq, nr, rng):
    spec, params = kernels_zoo.make(kid)
    if spec.band is not None and abs(nq - nr) > spec.band:
        pytest.skip("corner outside band")
    q, r = make_kernel_inputs(rng, spec, nq, nr)
    lens = np.asarray([nq, nr], np.int32)
    from repro.kernels.wavefront import kernel as K
    import jax.numpy as jnp
    pad = (-nq) % n_pe
    qp = jnp.concatenate(
        [q, jnp.zeros((pad,) + q.shape[1:], q.dtype)]) if pad else q
    tb, best, best_j = K.wavefront_fill(spec, params, qp[None], r[None],
                                        lens[None], n_pe=n_pe,
                                        interpret=True)
    o_best, o_best_j, o_tb = wref.run(spec, params, np.asarray(qp), r,
                                      nq, nr, n_pe=n_pe)
    np.testing.assert_allclose(np.asarray(best[0]), o_best, rtol=1e-5,
                               err_msg="per-lane best mismatch")
    valid = o_best > float(np.asarray(spec.sentinel())) / 2 \
        if not spec.is_min else o_best < float(np.asarray(spec.sentinel())) / 2
    np.testing.assert_array_equal(np.asarray(best_j[0])[valid],
                                  o_best_j[valid])
    if spec.traceback is None:
        assert tb is None
    else:
        np.testing.assert_array_equal(np.asarray(tb[0]), o_tb)


@pytest.mark.parametrize("kid", [1, 2, 4, 9, 15])
def test_end_to_end_alignment_via_pallas(kid, rng):
    """Full align() through the Pallas engine == reference engine."""
    spec, params = kernels_zoo.make(kid)
    q, r = make_kernel_inputs(rng, spec, 48, 56)
    a_ref = align(spec, params, q, r, engine_name="reference")
    a_pl = align(spec, params, q, r, engine_name="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a_ref.score),
                               np.asarray(a_pl.score), rtol=1e-5)
    if spec.traceback is not None:
        from repro.core import rescore
        got = rescore.rescore(spec, params, q, r, a_pl)
        assert abs(got - float(a_pl.score)) < 1e-3


def test_pallas_effective_lengths(rng):
    spec, params = kernels_zoo.make(2)
    q, r = make_kernel_inputs(rng, spec, 64, 64)
    a_full = align(spec, params, q[:40], r[:44], engine_name="reference",
                   with_traceback=False)
    res = wops.run(spec, params, q, r, q_len=40, r_len=44, interpret=True,
                   n_pe=16)
    assert int(res.score) == int(a_full.score)
