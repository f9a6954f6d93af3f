"""All 15 Table-1 kernels: engine equivalence + path validity.

For every kernel: the wavefront back-end must reproduce the reference
(row-major oracle) optimum; for kernels with traceback, the reported path
must re-score to the reported score (tie-break-agnostic oracle) and land
on the reported end cell.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import align, kernels_zoo, rescore
from repro.core import types as T

from conftest import make_kernel_inputs, seed_plan

ALL_KERNELS = list(range(1, 16))


@pytest.mark.parametrize("kid", ALL_KERNELS)
@pytest.mark.parametrize("nq,nr", [(32, 32), (48, 31), (17, 63)])
def test_wavefront_matches_reference(kid, nq, nr, rng):
    spec, params = kernels_zoo.make(kid)
    if spec.band is not None and abs(nq - nr) > spec.band:
        pytest.skip("corner outside band")
    q, r = make_kernel_inputs(rng, spec, nq, nr)
    a_ref = align(spec, params, q, r, engine_name="reference",
                  with_traceback=False)
    a_wf = align(spec, params, q, r, engine_name="wavefront",
                 with_traceback=False)
    np.testing.assert_allclose(np.asarray(a_ref.score),
                               np.asarray(a_wf.score), rtol=1e-5)


@pytest.mark.parametrize("kid", ALL_KERNELS)
def test_effective_lengths(kid, rng):
    """Padded inputs with explicit lengths == exact-size inputs."""
    spec, params = kernels_zoo.make(kid)
    q, r = make_kernel_inputs(rng, spec, 24, 28)
    qp, rp = make_kernel_inputs(rng, spec, 40, 40)
    qp = qp.at[:24].set(q) if hasattr(qp, "at") else qp
    rp = rp.at[:28].set(r)
    a = align(spec, params, q, r, with_traceback=False)
    b = align(spec, params, qp.at[:24].set(q), rp, q_len=24, r_len=28,
              with_traceback=False)
    np.testing.assert_allclose(np.asarray(a.score), np.asarray(b.score),
                               rtol=1e-5)


@pytest.mark.parametrize("kid", [k for k in ALL_KERNELS
                                 if kernels_zoo.make(k)[0].traceback
                                 is not None])
@pytest.mark.parametrize("engine", ["reference", "wavefront"])
def test_path_rescores_to_score(kid, engine, rng):
    spec, params = kernels_zoo.make(kid)
    nq, nr = 40, 44
    if spec.band is not None and abs(nq - nr) > spec.band:
        nq = nr
    q, r = make_kernel_inputs(rng, spec, nq, nr)
    a = align(spec, params, q, r, engine_name=engine)
    got = rescore.rescore(spec, params, q, r, a)
    assert abs(got - float(a.score)) < 1e-3, (
        f"path rescored to {got}, engine reported {float(a.score)}")


@pytest.mark.parametrize("kid", ALL_KERNELS)
def test_packed_strip_fill_bit_identical_to_seed(kid, rng):
    """The optimized hot path — bit-packed traceback, strip-mined /
    early-exit fill, batched traceback walk — must produce bit-identical
    (score, start, end, moves) vs the seed schedule (strip=1, one byte
    per pointer, full-bucket fill) for every zoo kernel."""
    import jax.numpy as jnp
    from repro.runtime import plan as plan_mod

    spec, params = kernels_zoo.make(kid)
    B, bucket = 5, 64
    qs = jnp.stack([make_kernel_inputs(rng, spec, bucket, bucket)[0]
                    for _ in range(B)])
    rs = jnp.stack([make_kernel_inputs(rng, spec, bucket, bucket)[1]
                    for _ in range(B)])
    ql = jnp.asarray(rng.integers(4, bucket + 1, B), jnp.int32)
    rl = jnp.asarray(rng.integers(4, bucket + 1, B), jnp.int32)
    if spec.band is not None:
        rl = ql                      # keep the corner inside the band

    seed = seed_plan(spec, "wavefront", bucket)
    char = spec.char_shape
    opt = plan_mod.get_plan(spec, "wavefront", (bucket,) + char,
                            (bucket,) + char, batch_size=B)

    a = seed(params, qs, rs, ql, rl)
    b = opt(params, qs, rs, ql, rl)
    fields = ["score", "end_i", "end_j"]
    if spec.traceback is not None:
        fields += ["start_i", "start_j", "n_moves", "moves"]
        assert not np.asarray(b.truncated).any()
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{spec.name}: {f}")


def test_local_score_nonnegative(rng):
    spec, params = kernels_zoo.make(3)
    q, r = make_kernel_inputs(rng, spec, 16, 16)
    a = align(spec, params, q, r, with_traceback=False)
    assert float(a.score) >= 0


def test_affine_equals_linear_when_flat(rng):
    """Gotoh with gap_open == gap_extend degenerates to linear gaps."""
    from repro.core.kernels_zoo import dna_affine, dna_linear
    g = -3
    spec_a = dna_affine.global_affine()
    params_a = dna_affine.default_params(gap_open=g, gap_extend=g)
    spec_l = dna_linear.global_linear()
    params_l = dna_linear.default_params(gap=g)
    rng_ = np.random.default_rng(7)
    q, r = make_kernel_inputs(rng_, spec_l, 37, 41)
    sa = align(spec_a, params_a, q, r, with_traceback=False).score
    sl = align(spec_l, params_l, q, r, with_traceback=False).score
    assert int(sa) == int(sl)


def test_two_piece_equals_affine_when_identical_pieces(rng):
    from repro.core.kernels_zoo import dna_affine, dna_two_piece
    spec_tp = dna_two_piece.global_two_piece()
    params_tp = dna_two_piece.default_params(
        match=2, mismatch=-3, gap_open=-5, gap_extend=-1,
        gap_open2=-5, gap_extend2=-1)
    spec_a = dna_affine.global_affine()
    params_a = dna_affine.default_params()
    q, r = make_kernel_inputs(rng, spec_a, 30, 34)
    s1 = align(spec_tp, params_tp, q, r, with_traceback=False).score
    s2 = align(spec_a, params_a, q, r, with_traceback=False).score
    assert int(s1) == int(s2)


def test_banded_equals_full_when_band_covers(rng):
    from repro.core.kernels_zoo import dna_linear
    spec_b = dna_linear.banded_global_linear(band=128)
    spec_f = dna_linear.global_linear()
    params = dna_linear.default_params()
    q, r = make_kernel_inputs(rng, spec_f, 40, 40)
    sb = align(spec_b, params, q, r, with_traceback=False).score
    sf = align(spec_f, params, q, r, with_traceback=False).score
    assert int(sb) == int(sf)


def test_global_symmetry(rng):
    """NW with symmetric scoring: score(q, r) == score(r, q)."""
    spec, params = kernels_zoo.make(1)
    q, r = make_kernel_inputs(rng, spec, 25, 33)
    s1 = align(spec, params, q, r, with_traceback=False).score
    s2 = align(spec, params, r, q, with_traceback=False).score
    assert int(s1) == int(s2)


def _nw_loop(q, r, match, mismatch, gap):
    """Textbook row-major Needleman-Wunsch with a linear gap, score only."""
    prev = gap * np.arange(len(r) + 1)
    for i in range(1, len(q) + 1):
        cur = np.empty_like(prev)
        cur[0] = gap * i
        for j in range(1, len(r) + 1):
            sub = match if q[i - 1] == r[j - 1] else mismatch
            cur[j] = max(prev[j - 1] + sub, prev[j] + gap, cur[j - 1] + gap)
        prev = cur
    return int(prev[-1])


def test_batched_nw_matches_textbook_loop(rng):
    """The spec-driven batched engine's global_linear scores equal an
    independent row-major loop that knows nothing of the DP spec."""
    from repro.core import batch as core_batch
    spec, params = kernels_zoo.make(1)
    qs = rng.integers(0, 4, (4, 96)).astype(np.uint8)
    rs = rng.integers(0, 4, (4, 96)).astype(np.uint8)
    n = np.full(4, 96, np.int32)
    got = core_batch.align_batch(spec, params, qs, rs, n, n,
                                 with_traceback=False).score
    sc = {k: int(v) for k, v in params.items()}
    want = [_nw_loop(q, r, sc["match"], sc["mismatch"], sc["gap"])
            for q, r in zip(qs, rs)]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_identity_alignment_scores_perfect(rng):
    spec, params = kernels_zoo.make(1)
    q, _ = make_kernel_inputs(rng, spec, 30, 30)
    a = align(spec, params, q, q)
    assert int(a.score) == 30 * 2          # match bonus = 2
    from repro.core.traceback import moves_to_cigar
    assert moves_to_cigar(a.moves, a.n_moves) == "30M"


def test_dtw_identical_signals_zero(rng):
    spec, params = kernels_zoo.make(9)
    q, _ = make_kernel_inputs(rng, spec, 20, 20)
    a = align(spec, params, q, q, with_traceback=False)
    assert abs(float(a.score)) < 1e-5
