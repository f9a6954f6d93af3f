"""Point-scope lint rules: recurrence legality, retrace/transfer
hazards, and Pallas budgets, all from abstract traces (no compiles).

Rule IDs are grouped by family (the paper's synthesis-time checks,
transplanted to trace time):

  * R1xx recurrence legality — the declarative kernel spec really is the
    recurrence the systolic template can schedule;
  * R2xx retrace/recompile hazards — one logical plan point must map to
    one cache entry with stable dtypes;
  * R3xx transfer/sync — nothing in a jitted fill round-trips the host;
  * R4xx budgets — Pallas VMEM blocks and traceback stores fit.

Each rule is ``fn(ctx, cfg) -> iterable[Finding]`` over a
:class:`~repro.analyze.context.PointContext`; ``scope='kernel'`` rules
are engine-independent and run once per kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spec_utils
from repro.launch import hlo_cost
from repro.runtime import plan as plan_mod
from repro.runtime import registry

from .findings import ERROR, INFO, WARNING, Finding


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    title: str
    severity: str                 # default severity of its findings
    scope: str                    # 'point' | 'kernel' | 'global'
    fn: Callable
    doc: str = ""


# ---------------------------------------------------------------------------
# R1xx — recurrence legality
# ---------------------------------------------------------------------------
def rule_pe_abstract(ctx, cfg) -> Iterator[Finding]:
    """R101: the PE/init declarations satisfy the engine cell contract.

    Every engine schedules the recurrence through the fixed neighbor set
    ``spec_utils.WAVEFRONT_NEIGHBORS`` and trusts the PE to return
    ``(scores[n_layers], ptr)`` in the declared dtypes; the boundary
    initializers must produce ``n*n_layers`` scores without a lossy
    cast.  A violation mis-fills on *every* engine, so this runs once
    per kernel."""
    spec = ctx.spec
    where = spec.name
    try:
        scores, ptr = spec_utils.pe_abstract_eval(spec, ctx.params)
    except Exception as e:
        yield Finding("R101", ERROR,
                      f"PE failed abstract evaluation at the engine cell "
                      f"contract (params, q_char, r_char, diag[L], up[L], "
                      f"left[L], i, j): {type(e).__name__}: {e}", where)
        return
    if tuple(scores.shape) != (spec.n_layers,):
        yield Finding("R101", ERROR,
                      f"PE returns scores of shape {tuple(scores.shape)}, "
                      f"declared n_layers={spec.n_layers} requires "
                      f"({spec.n_layers},)", where)
    want = jnp.dtype(spec.score_dtype)
    if scores.dtype != want:
        yield Finding("R101", ERROR,
                      f"PE returns {scores.dtype} scores but the spec "
                      f"declares score_dtype={want.name} — the engines' "
                      f"cast would silently truncate/promote every cell",
                      where)
    if spec.traceback is not None:
        if tuple(ptr.shape) != ():
            yield Finding("R101", ERROR,
                          f"PE traceback pointer must be a scalar, got "
                          f"shape {tuple(ptr.shape)}", where)
        if not jnp.issubdtype(ptr.dtype, jnp.integer):
            yield Finding("R101", ERROR,
                          f"PE traceback pointer must be an integer, got "
                          f"{ptr.dtype}", where)
    n = 8
    try:
        row, col = spec_utils.init_abstract_eval(spec, ctx.params, n)
    except Exception as e:
        yield Finding("R101", ERROR,
                      f"boundary initializer failed abstract evaluation: "
                      f"{type(e).__name__}: {e}", where)
        return
    for name, aval in (("init_row", row), ("init_col", col)):
        size = int(np.prod(aval.shape)) if aval.shape else 1
        if size != n * spec.n_layers:
            yield Finding("R101", ERROR,
                          f"{name} returns {size} scores for {n} indices; "
                          f"engines reshape to (n, n_layers={spec.n_layers})",
                          where)
        if (jnp.issubdtype(aval.dtype, jnp.floating)
                and jnp.issubdtype(want, jnp.integer)):
            yield Finding("R101", ERROR,
                          f"{name} returns {aval.dtype} for integer "
                          f"score_dtype={want.name} — the engines' "
                          f"asarray cast truncates boundary scores", where)


def rule_band_reach(ctx, cfg) -> Iterator[Finding]:
    """R102: banded kernels can actually reach their objective region at
    the linted bucket shape.  With a fixed band |i−j| ≤ W, a corner
    objective at (Q, R) is outside the band whenever |Q−R| > W — every
    cell of the region is pruned and the plan returns the sentinel for
    *all* inputs.  The paper's synthesis-time banding check, at trace
    time."""
    spec = ctx.spec
    if spec.band is None:
        return
    W = int(spec.band)
    Q, R = ctx.point.bucket
    where = f"{spec.name} {Q}x{R}"
    if W < 1:
        yield Finding("R102", ERROR,
                      f"band width {W} prunes the whole matrix", where)
        return
    gap = None
    from repro.core import types as T
    if spec.region == T.REGION_CORNER:
        gap = abs(Q - R)
    elif spec.region == T.REGION_LAST_ROW:
        gap = Q - R                     # nearest last-row cell is (Q, R)
    if gap is not None and gap > W:
        yield Finding("R102", ERROR,
                      f"objective region {spec.region!r} unreachable: "
                      f"bucket {Q}x{R} needs |i-j| = {gap} > band {W} — "
                      f"every plan at this bucket returns the sentinel",
                      where)


def rule_unit_cost(ctx, cfg) -> Iterator[Finding]:
    """R103: the myers engines' unit-cost precondition really holds.
    They never consult ``spec.pe`` — the bit-vector recurrence *is*
    Levenshtein — so a kernel admitted by name whose PE or boundary
    init is not unit-cost silently computes the wrong distance.  Probe
    the declared recurrence on concrete cells and compare against
    ``min(diag + [q≠r], up+1, left+1)``."""
    if not ctx.point.engine.startswith("myers"):
        return
    spec, params = ctx.spec, ctx.params
    where = f"{spec.name}×{ctx.point.engine}"
    from repro.core import types as T
    probes = [(0, 0, 3, 5, 7), (0, 1, 2, 2, 2), (1, 3, 0, 9, 1),
              (2, 2, 4, 0, 5)]
    try:
        for q, r, d, u, lft in probes:
            qc = jnp.asarray(q, spec.char_dtype)
            rc = jnp.asarray(r, spec.char_dtype)
            cell = lambda v: jnp.asarray([v], spec.score_dtype)
            scores, _ = spec.pe(params, qc, rc, cell(d), cell(u), cell(lft),
                                jnp.int32(1), jnp.int32(1))
            got = int(jnp.asarray(scores).reshape(-1)[0])
            want = min(d + (0 if q == r else 1), u + 1, lft + 1)
            if got != want:
                yield Finding("R103", ERROR,
                              f"PE is not the unit-cost recurrence: at "
                              f"(q={q}, r={r}, diag={d}, up={u}, "
                              f"left={lft}) PE gives {got}, Levenshtein "
                              f"gives {want} — the bit-parallel engine "
                              f"would silently disagree", where)
                return
        idx = jnp.arange(4, dtype=jnp.int32)
        col = np.asarray(spec.init_col(params, idx)).reshape(-1)[:4]
        if not np.array_equal(col, np.arange(4)):
            yield Finding("R103", ERROR,
                          f"init_col must be D[i][0] = i for the unit-cost "
                          f"recurrence, got {col.tolist()}", where)
        row = np.asarray(spec.init_row(params, idx)).reshape(-1)[:4]
        want_row = (np.arange(4) if spec.region == T.REGION_CORNER
                    else np.zeros(4))
        if not np.array_equal(row, want_row):
            yield Finding("R103", ERROR,
                          f"init_row must be {want_row.astype(int).tolist()} "
                          f"for region {spec.region!r}, got {row.tolist()} — "
                          f"the myers engine's hin convention would diverge",
                          where)
    except Exception as e:
        yield Finding("R103", ERROR,
                      f"unit-cost probe failed: {type(e).__name__}: {e}",
                      where)


# ---------------------------------------------------------------------------
# R2xx — retrace / recompile hazards
# ---------------------------------------------------------------------------
def rule_plan_key(ctx, cfg) -> Iterator[Finding]:
    """R201: one logical plan point = one cache entry.  The spec and
    every resolved option must be hashable (they form the cache key — an
    unhashable leaf raises at dispatch), and option resolution must be
    deterministic (two identical requests that resolve differently
    compile two executables for one schedule)."""
    where = ctx.point.label
    try:
        hash(ctx.spec)
    except TypeError as e:
        yield Finding("R201", ERROR,
                      f"kernel spec is unhashable ({e}) — get_plan's cache "
                      f"key raises at every dispatch (check tuple-valued "
                      f"fields like char_shape)", where)
        return
    try:
        opts_a = dict(ctx.options)
        opts_b = plan_mod.resolve_engine_options(
            ctx.spec, ctx.point.engine, {})
        opts_c = plan_mod.resolve_engine_options(
            ctx.spec, ctx.point.engine, {})
    except Exception as e:
        yield Finding("R201", ERROR,
                      f"engine option resolution failed: "
                      f"{type(e).__name__}: {e}", where)
        return
    if opts_b != opts_c:
        yield Finding("R201", ERROR,
                      f"option resolution is nondeterministic: two empty "
                      f"requests resolved to {opts_b} and {opts_c} — every "
                      f"dispatch re-traces under a fresh key", where)
    for name, value in sorted(opts_a.items()):
        try:
            hash(value)
        except TypeError:
            yield Finding("R201", ERROR,
                          f"resolved option {name}={value!r} is unhashable "
                          f"— PlanKey/cache-key construction raises", where)
    try:
        hash(ctx.key)
    except TypeError as e:
        yield Finding("R201", ERROR, f"PlanKey unhashable: {e}", where)


def rule_dtype_drift(ctx, cfg) -> Iterator[Finding]:
    """R202: the abstract output of exactly the program the cache would
    jit keeps the declared dtypes.  Catches x64-off downcasts (a spec
    declaring float64 silently computes float32), x64-on promotion
    drift, and weak-typed output leaves (weak leaves re-trace against
    strong-typed callers)."""
    where = ctx.point.label
    try:
        out = ctx.out_avals
    except Exception as e:
        yield Finding("R202", ERROR,
                      f"plan fails abstract tracing: "
                      f"{type(e).__name__}: {e}", where)
        return
    want = jnp.dtype(ctx.spec.score_dtype)
    got = jnp.dtype(out.score.dtype)
    if got != want:
        x64 = jax.config.jax_enable_x64
        hint = ("x64 is disabled: 64-bit declarations silently downcast"
                if want.itemsize == 8 and not x64 else "promotion drift")
        yield Finding("R202", ERROR,
                      f"declared score_dtype={want.name} but the traced "
                      f"plan returns {got.name} ({hint})", where)
    for leaf in jax.tree_util.tree_leaves(out):
        if getattr(leaf, "weak_type", False):
            yield Finding("R202", WARNING,
                          f"weak-typed output leaf {leaf.dtype} — mixing "
                          f"with strong-typed callers re-traces per call "
                          f"site", where)


def rule_x64_params(ctx, cfg) -> Iterator[Finding]:
    """R203: parameter pytrees carry no 64-bit or weak-typed leaves.
    A ``np.float64`` scalar param is downcast silently when x64 is off
    and doubles every buffer (and splits tuned schedules) when it is
    on; python-float leaves trace weak-typed and are a retrace hazard.
    Engine-independent, so runs once per kernel."""
    spec = ctx.spec
    leaves, _ = jax.tree_util.tree_flatten(ctx.params)
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, bool):
            continue
        if isinstance(leaf, float):
            yield Finding("R203", WARNING,
                          f"param leaf #{i} is a python float "
                          f"({leaf!r}) — traces weak-typed; wrap in "
                          f"jnp.asarray with an explicit dtype", spec.name)
            continue
        if isinstance(leaf, int):
            continue                   # static ints are common and safe
        arr = np.asarray(leaf)
        if arr.dtype.kind in "fiu" and arr.dtype.itemsize == 8:
            yield Finding("R203", WARNING,
                          f"param leaf #{i} is {arr.dtype} — silently "
                          f"downcast with x64 off, doubles buffers/splits "
                          f"plan keys with x64 on", spec.name)


# ---------------------------------------------------------------------------
# R3xx — transfer / sync lints
# ---------------------------------------------------------------------------
# host round-trips whose primitive name does not say "callback"
# (``jax.debug.print`` traces to ``debug_print``)
_HOST_PRIMS = frozenset({"debug_print", "infeed", "outfeed"})


def rule_host_callback(ctx, cfg) -> Iterator[Finding]:
    """R301: no host callbacks inside the traced fill.  A
    ``pure_callback``/``io_callback``/``debug_callback`` (e.g. a stray
    ``jax.debug.print``) in a kernel PE stalls the device pipeline on
    every dispatch — exactly the transfer hazard the serving path's
    async dispatch exists to avoid."""
    where = ctx.point.label
    try:
        prims = ctx.primitives
    except Exception as e:
        yield Finding("R301", ERROR,
                      f"plan fails jaxpr tracing: {type(e).__name__}: {e}",
                      where)
        return
    bad = sorted(p for p in prims if "callback" in p or p in _HOST_PRIMS)
    for p in bad:
        yield Finding("R301", ERROR,
                      f"traced plan contains host callback primitive "
                      f"{p!r} — every dispatch synchronizes device→host",
                      where)


def rule_const_capture(ctx, cfg) -> Iterator[Finding]:
    """R302: no large constant-folded array captures.  An array closed
    over by a PE (or materialized at trace time) becomes a jaxpr
    constant baked into *every* executable that shares the kernel —
    the classic tracer-leak that bloats HLO and compile times across
    the whole bucket grid."""
    where = ctx.point.label
    try:
        consts = ctx.consts
    except Exception as e:
        yield Finding("R302", ERROR,
                      f"plan fails jaxpr tracing: {type(e).__name__}: {e}",
                      where)
        return
    for shape, dtype, nbytes in consts:
        if nbytes >= cfg.const_error_bytes:
            yield Finding("R302", ERROR,
                          f"trace captured a {nbytes >> 20} MiB constant "
                          f"{dtype}{list(shape)} — baked into every "
                          f"executable of this kernel (tracer leak)", where)
        elif nbytes >= cfg.const_warn_bytes:
            yield Finding("R302", WARNING,
                          f"trace captured a {nbytes >> 10} KiB constant "
                          f"{dtype}{list(shape)}; prefer passing it as a "
                          f"param so executables share one buffer", where)


def rule_hlo_transfer(ctx, cfg) -> Iterator[Finding]:
    """R303: the lowered HLO contains no host-transfer instructions
    (callback custom-calls, infeed/outfeed, send/recv).  The HLO-level
    twin of R301 — it also sees transfers introduced below the jaxpr
    (engine internals, lowering rules).  Skipped when the engine cannot
    lower on this backend (pallas TPU kernels on CPU hosts)."""
    if not cfg.hlo_rules:
        return
    text = ctx.hlo
    where = ctx.point.label
    if text is None:
        yield Finding("R303", INFO,
                      "lowering unavailable on this backend; HLO-level "
                      "transfer scan skipped", where)
        return
    for comp, op, detail in hlo_cost.host_transfer_instrs(text):
        yield Finding("R303", WARNING,
                      f"lowered HLO computation {comp!r} contains host "
                      f"transfer {op} ({detail})", where)


# ---------------------------------------------------------------------------
# R4xx — Pallas / memory budgets
# ---------------------------------------------------------------------------
def rule_pallas_vmem(ctx, cfg) -> Iterator[Finding]:
    """R401: the Pallas kernel's per-grid-step VMEM blocks fit the
    backend budget.  Pure shape arithmetic over the same BlockSpecs the
    launch declares — the paper's BRAM-capacity synthesis check; an
    over-budget block is an OOM at first dispatch, hours into a
    benchmark run."""
    eng = ctx.point.engine
    if "pallas" not in eng:
        return
    Q, R = ctx.point.bucket
    where = ctx.point.label
    if eng.startswith("myers"):
        from repro.kernels.myers import ops as mops
        est = mops.vmem_bytes(ctx.spec, Q, R)
    else:
        from repro.kernels.wavefront import ops as wops
        est = wops.vmem_bytes(ctx.spec, Q, R, params=ctx.params,
                              n_pe=plan_mod.PALLAS_N_PE,
                              tb_pack=ctx.options["tb_pack"])
    if est > cfg.vmem_budget_bytes:
        yield Finding("R401", ERROR,
                      f"estimated VMEM {est >> 20} MiB exceeds the "
                      f"{cfg.vmem_budget_bytes >> 20} MiB budget — the "
                      f"kernel OOMs at first dispatch; shrink the bucket "
                      f"or tile the reference", where)
    elif est > cfg.vmem_budget_bytes // 2:
        yield Finding("R401", WARNING,
                      f"estimated VMEM {est >> 20} MiB is over half the "
                      f"{cfg.vmem_budget_bytes >> 20} MiB budget", where)


def rule_pallas_grid(ctx, cfg) -> Iterator[Finding]:
    """R402: grid/block divisibility.  The wavefront launch *silently*
    resets ``tb_pack`` to 1 when it does not divide the lane strip —
    legal, but the caller's memory budget is then 2-4x off; lane-strip
    padding waste is surfaced as info."""
    eng = ctx.point.engine
    if not (eng.startswith("pallas")):
        return
    where = ctx.point.label
    n_pe = plan_mod.PALLAS_N_PE
    pack = ctx.options["tb_pack"]
    if pack and n_pe % pack:
        yield Finding("R402", WARNING,
                      f"tb_pack={pack} does not divide the n_pe={n_pe} "
                      f"lane strip — the launch silently resets it to 1 "
                      f"and the traceback store grows {pack}x", where)
    Q = ctx.point.bucket[0]
    if Q % n_pe:
        padded = -(-Q // n_pe) * n_pe
        yield Finding("R402", INFO,
                      f"query bucket {Q} pads to {padded} lanes "
                      f"({100 * (padded - Q) // padded}% idle PEs); "
                      f"bucket to a multiple of {n_pe}", where)


def rule_tb_budget(ctx, cfg) -> Iterator[Finding]:
    """R403: the block's traceback store fits the serving memory budget.
    ``traceback_bytes × batch`` is the per-block HBM the services size
    their queues by; a block that cannot fit should be split before
    benchmark time, not discovered as an OOM there."""
    p = ctx.point
    if not p.with_traceback or p.batch_size is None:
        return
    sup = registry.engine_options(p.engine)
    kw = {}
    if "strip" in sup:
        kw["strip"] = ctx.options["strip"]
    if "tb_pack" in sup:
        kw["tb_pack"] = ctx.options["tb_pack"]
    per = plan_mod.traceback_bytes(ctx.spec, p.bucket[0], p.bucket[1],
                                   engine_name=p.engine, **kw)
    total = per * p.batch_size
    if total > cfg.tb_budget_bytes:
        yield Finding("R403", WARNING,
                      f"traceback store {total >> 20} MiB "
                      f"({per} B × batch {p.batch_size}) exceeds the "
                      f"{cfg.tb_budget_bytes >> 20} MiB block budget — "
                      f"split the block or raise tb_pack",
                      p.label)


POINT_RULES: List[Rule] = [
    Rule("R101", "pe-contract", ERROR, "kernel", rule_pe_abstract,
         "PE/init abstract shapes and dtypes match the spec declaration"),
    Rule("R102", "band-reach", ERROR, "kernel", rule_band_reach,
         "banded objective region reachable at the linted bucket"),
    Rule("R103", "unit-cost", ERROR, "point", rule_unit_cost,
         "myers engines' hard-coded recurrence matches the kernel PE"),
    Rule("R201", "plan-key", ERROR, "point", rule_plan_key,
         "hashable, deterministic plan cache keys"),
    Rule("R202", "dtype-drift", ERROR, "point", rule_dtype_drift,
         "traced output dtypes match declarations; no weak-type leaks"),
    Rule("R203", "x64-params", WARNING, "kernel", rule_x64_params,
         "no 64-bit or weak-typed parameter leaves"),
    Rule("R301", "host-callback", ERROR, "point", rule_host_callback,
         "no host callback primitives in the traced plan"),
    Rule("R302", "const-capture", WARNING, "point", rule_const_capture,
         "no large constant-folded array captures in the jaxpr"),
    Rule("R303", "hlo-transfer", WARNING, "point", rule_hlo_transfer,
         "no host-transfer instructions in the lowered HLO"),
    Rule("R401", "pallas-vmem", ERROR, "point", rule_pallas_vmem,
         "Pallas per-step VMEM estimate within the backend budget"),
    Rule("R402", "pallas-grid", WARNING, "point", rule_pallas_grid,
         "grid/block divisibility; no silent tb_pack fallback"),
    Rule("R403", "tb-budget", WARNING, "point", rule_tb_budget,
         "block traceback store within the serving memory budget"),
]
