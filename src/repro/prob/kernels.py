"""GATK-style pair-HMM kernels, semiring-generic (forward / viterbi / backward).

One PE template covers the whole family: written against
``semiring.combine``, it is the Viterbi scorer under max-plus and the
forward-likelihood recurrence under log-sum-exp — the AnySeq
"same recurrence, different scoring semantics" observation, running on
the unchanged wavefront/reference/Pallas back-ends.

Model (read x on the query axis, haplotype y on the reference axis):

  * states M (match/mismatch, consumes both), X (read insertion,
    consumes a read base — the engines' *up* move) and Y (haplotype
    gap, consumes a hap base — the *left* move);
  * transitions  M->X = M->Y = delta (gap open),  X->X = Y->Y = eps
    (gap extend),  X->M = Y->M = 1 - eps,  M->M = 1 - 2*delta;
    X<->Y is forbidden;
  * emissions: a 5x5 substitution table for M, a flat ``gap_emission``
    for X/Y (parameter layout shared with the zoo's Viterbi kernel #10
    — the same ``default_params`` dict drives both);
  * free start/end along the haplotype (the GATK convention): row 0
    carries unit mass in Y at every column (a read may enter anywhere
    in the haplotype) and the likelihood sums M+X over the last row (it
    may leave anywhere).  The reported likelihood is therefore
    *unnormalized* over start positions — divide by the haplotype
    length (subtract ``log r_len``) to compare across haplotypes, as
    ``repro.prob.genotype`` does.

Layers: ``[M, X, Y, F]`` with ``F = M ⊕ X`` — the termination-eligible
mass per cell, so ``region=LAST_ROW`` + the sum semiring's region fold
yields ``logsumexp_j F(q_len, j)``: the forward likelihood.  Under
max-plus the same spec scores the best semiglobal Viterbi path.

``pairhmm_backward`` is the suffix recurrence *as a forward-style fill
over reversed sequences*: cell (i', j') of the backward fill holds
``B(q_len - i', r_len - j')`` — see ``repro.prob.posterior`` for the
index algebra and the forward·backward combination.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core import semiring as S
from repro.core import types as T
from repro.core.kernels_zoo import viterbi as viterbi_mod

_DEAD = -1e30

# the zoo Viterbi kernel's parameter dict IS this family's parameter
# dict (delta/eps/match_p -> log-space transitions + 5x5 emissions)
default_params = viterbi_mod.default_params


def _forward_pe(sr: S.Semiring):
    """Semiring-generic forward PE: ⊕ over incoming transitions.

    Layer order [M, X, Y, F]; ``up`` consumes a read base (X), ``left``
    a haplotype base (Y).
    """
    def pe(params, q, r, diag, up, left, i, j):
        em = params["emission"][q.astype(jnp.int32), r.astype(jnp.int32)]
        t_open = params["log_lambda"]    # M -> X/Y (gap open)
        t_ext = params["log_mu"]         # X -> X / Y -> Y (gap extend)
        ge = params["gap_emission"]
        m = em + sr.combine(diag[0] + params["t_mm"],
                            sr.combine(diag[1], diag[2]) + params["t_gm"])
        x = ge + sr.combine(up[0] + t_open, up[1] + t_ext)
        y = ge + sr.combine(left[0] + t_open, left[2] + t_ext)
        f = sr.combine(m, x)             # termination-eligible mass
        return jnp.stack([m, x, y, f]), jnp.int32(0)
    return pe


def _forward_init_row(params, j):
    """Free start along the haplotype: unit mass in Y at every column
    (GATK's D-row initialization), M/X/F unreachable."""
    y = jnp.zeros_like(j, jnp.float32)
    dead = jnp.full_like(y, _DEAD)
    return jnp.stack([dead, dead, y, dead], axis=-1)


def _forward_init_col(params, i):
    """Column 0: only the (0, 0) start cell is live (a read cannot be
    consumed before the path enters the haplotype — X<->Y forbidden)."""
    y = jnp.where(i == 0, 0.0, _DEAD).astype(jnp.float32)
    dead = jnp.full_like(y, _DEAD)
    return jnp.stack([dead, dead, y, dead], axis=-1)


def pairhmm(objective: str = "logsumexp", **kw) -> T.DPKernelSpec:
    """The pair-HMM spec at a chosen semiring.

    ``objective='logsumexp'`` (default) is the forward likelihood:
    score = log P(read | haplotype), summed over every alignment.
    ``objective='max'`` is the Viterbi mode of the identical model: the
    best single alignment's log-probability (always <= forward).
    ``band=W`` prunes |i - j| > W — the banded forward option (exact
    when the band covers every plausible diagonal).
    """
    sr = S.from_objective(objective)
    return T.DPKernelSpec(
        name=f"pairhmm_{sr.name}", n_layers=4,
        pe=_forward_pe(sr),
        init_row=_forward_init_row, init_col=_forward_init_col,
        objective=objective, region=T.REGION_LAST_ROW,
        score_dtype=jnp.float32, primary_layer=3,
        traceback=None, **kw)


# -- backward (suffix) recurrence -------------------------------------------
def _backward_pe(sr: S.Semiring):
    """Backward values as a forward-style fill over *reversed* inputs.

    Cell (i', j') holds B_S(i, j) = P(read suffix x[i+1:], exit | state
    S at (i, j)) with i = q_len - i', j = r_len - j'.  The engine hands
    this PE exactly the reversed-stream chars x[i+1], y[j+1] — the diag
    move's emission — and the up/left neighbors are B(i+1, j)/B(i, j+1).
    Transitions apply *leaving* S, so the transposed structure is:

      B_M = (t_mm + em) B_M(diag) ⊕ (delta + ge) B_X(up)
                                  ⊕ (delta + ge) B_Y(left)
      B_X = (t_gm + em) B_M(diag) ⊕ (eps + ge) B_X(up)
      B_Y = (t_gm + em) B_M(diag) ⊕ (eps + ge) B_Y(left)

    A fourth layer S = (t_gm + em) B_M(diag) is the *start mass*: the
    total probability of paths that enter the model at (i, j) — i.e.
    begin in the free-start Y row and immediately transition into M
    there.  It exists because the forward's row 0 is init-only (the
    free-start mass never chains Y(0,j) -> Y(0,j+1)), so B_Y on the
    backward's last row overcounts relative to the forward model; S is
    the row-0-consistent quantity, and its last-row fold is exactly Z.
    """
    def pe(params, q, r, diag, up, left, i, j):
        em = params["emission"][q.astype(jnp.int32), r.astype(jnp.int32)]
        t_open = params["log_lambda"]
        t_ext = params["log_mu"]
        ge = params["gap_emission"]
        to_m_from_m = params["t_mm"] + em + diag[0]
        to_m_from_gap = params["t_gm"] + em + diag[0]
        m = sr.combine(to_m_from_m,
                       sr.combine(t_open + ge + up[1],
                                  t_open + ge + left[2]))
        x = sr.combine(to_m_from_gap, t_ext + ge + up[1])
        y = sr.combine(to_m_from_gap, t_ext + ge + left[2])
        return jnp.stack([m, x, y, to_m_from_gap]), jnp.int32(0)
    return pe


def _backward_init_row(params, j):
    """Termination: the path exits at read row q_len from M or X with
    unit weight (row i' = 0 holds B(q_len, ·)); Y never terminates and
    no start can consume an already-exhausted read (S dead)."""
    z = jnp.zeros_like(j, jnp.float32)
    dead = jnp.full_like(z, _DEAD)
    return jnp.stack([z, z, dead, dead], axis=-1)


def _backward_init_col(params, i):
    """Column j' = 0 holds B(·, r_len): with the haplotype exhausted
    only X-chains remain — B_X(q_len - k, r_len) = (eps·ge)^k and
    B_M = delta·ge·(eps·ge)^(k-1) (one open, then extends)."""
    t_open = params["log_lambda"]
    t_ext = params["log_mu"]
    ge = params["gap_emission"]
    x = (i * (t_ext + ge)).astype(jnp.float32)
    m = jnp.where(i == 0, 0.0,
                  t_open + ge + (i - 1) * (t_ext + ge)).astype(jnp.float32)
    dead = jnp.full_like(x, _DEAD)
    return jnp.stack([m, x, dead, dead], axis=-1)


def pairhmm_backward(objective: str = "logsumexp", **kw) -> T.DPKernelSpec:
    """Backward pair-HMM fill (run it on *reversed* read/haplotype).

    With ``region=LAST_ROW`` over the start-mass layer S the spec's
    score is ``logsumexp_j S(0, j)`` — the total mass entering the
    model from the free-start row — which must equal the forward
    likelihood: the forward/backward consistency identity, asserted in
    tests.
    """
    sr = S.from_objective(objective)
    return T.DPKernelSpec(
        name=f"pairhmm_backward_{sr.name}", n_layers=4,
        pe=_backward_pe(sr),
        init_row=_backward_init_row, init_col=_backward_init_col,
        objective=objective, region=T.REGION_LAST_ROW,
        score_dtype=jnp.float32, primary_layer=3,
        traceback=None, **kw)


# One spec object per configuration: the plan cache keys executables by
# spec *identity-by-fields* (distinct constructions never share because
# their PE closures differ), so everything dispatching the same kernel —
# genotype.py, posterior.py, GenotypingService, the tests — must
# resolve its spec through these.
@functools.lru_cache(maxsize=None)
def cached_pairhmm(objective: str = "logsumexp", band=None) -> T.DPKernelSpec:
    return pairhmm(objective, band=band)


@functools.lru_cache(maxsize=None)
def cached_pairhmm_backward(objective: str = "logsumexp") -> T.DPKernelSpec:
    return pairhmm_backward(objective)
