"""CompiledPlan: one shared compile cache for matrix fill + traceback.

The paper synthesizes one fixed back-end per kernel configuration and
reuses it for every block/channel; the JAX analogue is one jitted
``fill (+ traceback)`` executable per ``(kernel, engine, bucket_shape,
batch_size, with_traceback)`` — memoized here so api/batch/serve/tiling
and the chip benchmark share a single cache instead of five independent
``jax.jit`` call sites, each re-tracing the same schedule.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.types as T
import repro.core.traceback as tb_mod

from repro.kernels.wavefront import N_PE as PALLAS_N_PE  # no Pallas import
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from . import registry

# Names of the plan program's two phases in the compiled program's
# ``op_name`` metadata (compile-time only: no device work, no switch), so
# a profiler trace can split device time into fill and traceback.
FILL_SCOPE = "plan.fill"
TRACEBACK_SCOPE = "plan.traceback"


def is_traced(*trees) -> bool:
    """True if any leaf of the given pytrees is a jax tracer — i.e. the
    caller is already inside a jit/vmap/scan trace and must inline
    rather than dispatch a CompiledPlan."""
    return any(isinstance(leaf, jax.core.Tracer)
               for tree in trees for leaf in jax.tree_util.tree_leaves(tree))


def align_impl(spec: T.DPKernelSpec, engine_fn: Callable, params,
               query, ref, q_len=None, r_len=None,
               with_traceback: bool = True):
    """Traceable fill + (optional) traceback for one pair.

    This is the single execution core: CompiledPlan jits it, and callers
    already inside a trace (vmap/jit/scan) inline it directly.
    """
    res = fill_impl(spec, engine_fn, params, query, ref, q_len, r_len)
    if with_traceback and spec.traceback is not None:
        max_len = query.shape[0] + ref.shape[0] + 1
        with jax.named_scope(TRACEBACK_SCOPE):
            return tb_mod.run(spec, res, max_len)
    return T.Alignment(score=res.score, end_i=res.end_i, end_j=res.end_j)


def fill_impl(spec: T.DPKernelSpec, engine_fn: Callable, params,
              query, ref, q_len=None, r_len=None) -> T.DPResult:
    with jax.named_scope(FILL_SCOPE):
        return engine_fn(spec, params, query, ref, q_len, r_len)


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Human-readable identity of a compiled plan (for cache_info)."""
    kernel: str
    engine: str
    bucket_shape: tuple              # ((Lq, *char), (Lr, *char))
    batch_size: Optional[int]        # None = single pair
    with_traceback: bool
    mode: str = "align"              # 'align' | 'fill'
    placement: Optional[str] = None  # e.g. 'data@data=8' for sharded plans
    strip: int = 1                   # anti-diagonals per scan step
    tb_pack: int = 1                 # traceback pointers packed per byte
    semiring: str = "maxplus"        # path algebra: maxplus|minplus|logsumexp
    xdrop: Optional[int] = None      # X-drop early termination; None = off


def plan_key_str(key: PlanKey) -> str:
    """Stable short string identity of a plan (the compile-ledger key):
    ``kernel/engine/QxR/bN/tb/mode/sSpP/semiring[/xN][/placement]``."""
    q, r = key.bucket_shape
    parts = [key.kernel, key.engine, f"{q[0]}x{r[0]}",
             "b1" if key.batch_size is None else f"b{key.batch_size}",
             "tb" if key.with_traceback else "notb", key.mode,
             f"s{key.strip}p{key.tb_pack}", key.semiring]
    if key.xdrop is not None:
        parts.append(f"x{key.xdrop}")
    if key.placement:
        parts.append(key.placement)
    return "/".join(parts)


def _build_fn(key: PlanKey, spec: T.DPKernelSpec,
              engine_name: str) -> Callable:
    """The pure python callable a plan jits: engine options applied,
    single vs batched dispatch resolved.  Shared by :class:`CompiledPlan`
    and :func:`lower_plan_hlo` so the cost model analyzes exactly the
    program the cache would compile."""
    engine_fn = registry.get_engine(engine_name)
    eng_opts = registry.engine_options(engine_name)
    # forward the plan's resolved schedule knobs (strip, tb_pack) to
    # engines that declare them; PlanKey fields are named after them.
    # 'dynamic'-valued options are runtime arguments, not cache knobs.
    opts = {name: getattr(key, name) for name, v in eng_opts.items()
            if v != "dynamic"}
    if opts:
        engine_fn = functools.partial(engine_fn, **opts)
    supports_bound = eng_opts.get("live_bound") == "dynamic"
    mode = key.mode
    wtb = key.with_traceback

    def single(params, query, ref, q_len, r_len):
        if mode == "fill":
            return fill_impl(spec, engine_fn, params, query, ref,
                             q_len, r_len)
        return align_impl(spec, engine_fn, params, query, ref,
                          q_len, r_len, with_traceback=wtb)

    if key.batch_size is None:
        return single

    # Batched: one shared fill bound (max over the block, passed
    # through vmap unbatched so the engine's early-exit loop
    # keeps a scalar counter), then — for traceback plans — one
    # batched walk over an active mask that terminates when
    # every row has hit its END pointer, instead of vmapping a
    # worst-case per-row while_loop.
    max_len = key.bucket_shape[0][0] + key.bucket_shape[1][0] + 1

    def eng(params, query, ref, q_len, r_len, bound):
        kw = {"live_bound": bound} if supports_bound else {}
        return engine_fn(spec, params, query, ref, q_len, r_len, **kw)

    def fn(params, queries, refs, q_lens, r_lens):
        with jax.named_scope(FILL_SCOPE):
            bound = jnp.max(q_lens + r_lens)
            res = jax.vmap(eng, in_axes=(None, 0, 0, 0, 0, None))(
                params, queries, refs, q_lens, r_lens, bound)
        if mode == "fill":
            return res
        if wtb:
            with jax.named_scope(TRACEBACK_SCOPE):
                return tb_mod.run_batched(spec, res, max_len=max_len)
        return T.Alignment(score=res.score, end_i=res.end_i,
                           end_j=res.end_j)

    return fn


class CompiledPlan:
    """A jitted alignment executable for one fixed input shape.

    Call as ``plan(params, query, ref, q_len, r_len)`` (arrays already
    padded to ``bucket_shape``; lengths scalar for single mode, ``(B,)``
    for batch mode).  ``calls`` counts dispatches into the shared
    executable.
    """

    def __init__(self, key: PlanKey, spec: T.DPKernelSpec,
                 engine_name: str, mesh=None, mesh_axis: str = "data"):
        self.key = key
        self.spec = spec
        self.calls = 0
        self.hits = 0          # cache hits after the initial miss
        self.compile_s = None  # trace+compile wall time of the first call
        self._avals = None     # argument shapes of the first call
        fn = _build_fn(key, spec, engine_name)

        # no input buffer is donated: no output has the shape and dtype
        # of the padded uint8 sequences, so XLA could never reuse one
        if mesh is None:
            self._fn = jax.jit(fn)
        else:
            # sharded plan: batch axis over ``mesh_axis``, params replicated
            # (the former private jit of core.batch.make_sharded_aligner,
            # folded into the shared cache)
            if key.batch_size is None:
                raise ValueError("sharded plans require batch_size")
            from jax.sharding import NamedSharding, PartitionSpec as P
            bsh = NamedSharding(mesh, P(mesh_axis))
            repl = NamedSharding(mesh, P())
            self._fn = jax.jit(
                fn, in_shardings=(repl, bsh, bsh, bsh, bsh),
                out_shardings=bsh)

    @property
    def batch_size(self):
        return self.key.batch_size

    def __call__(self, params, query, ref, q_len=None, r_len=None):
        q_shape, r_shape = self.key.bucket_shape
        if self.key.batch_size is None:
            q_len = q_shape[0] if q_len is None else q_len
            r_len = r_shape[0] if r_len is None else r_len
            q_len = jnp.asarray(q_len, jnp.int32)
            r_len = jnp.asarray(r_len, jnp.int32)
        else:
            n = self.key.batch_size
            if q_len is None:
                q_len = jnp.full((n,), q_shape[0], jnp.int32)
            if r_len is None:
                r_len = jnp.full((n,), r_shape[0], jnp.int32)
            q_len = jnp.asarray(q_len, jnp.int32)
            r_len = jnp.asarray(r_len, jnp.int32)
        self.calls += 1
        if self.compile_s is None:
            # first dispatch pays trace + compile synchronously; time it
            # (execution stays async, so this is compile-dominated)
            kstr = plan_key_str(self.key)
            self._avals = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.result_type(x)),
                (params, query, ref, q_len, r_len))
            with obs_trace.span("plan.compile", cat="plan", key=kstr):
                t0 = time.perf_counter()
                out = self._fn(params, query, ref, q_len, r_len)
                self.compile_s = time.perf_counter() - t0
            # the capped per-key ledger keeps this attribution across
            # clear_plan_cache(keep_stats=True)
            obs_metrics.record_compile(kstr, self.compile_s)
            obs_metrics.REGISTRY.counter("plan_compiles_total").inc()
            obs_metrics.REGISTRY.histogram("plan_compile_s").observe(
                self.compile_s)
            return out
        return self._fn(params, query, ref, q_len, r_len)

    def compiled_text(self) -> str:
        """XLA's text of the program this plan runs: its instructions as
        a profiler trace names its ops, each with its ``op_name``
        metadata.  Compiles again (a persistent-cache hit where the cache
        is on); only after the plan's first call."""
        if self._avals is None:
            raise RuntimeError(f"{self!r} has not run yet")
        return self._fn.lower(*self._avals).compile().as_text()

    def __repr__(self):
        return f"CompiledPlan({self.key}, calls={self.calls})"


# ---------------------------------------------------------------------------
# The shared cache.
# ---------------------------------------------------------------------------
_CACHE: dict[tuple, CompiledPlan] = {}
_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0}


def _placement(mesh, mesh_axis: str) -> Optional[str]:
    if mesh is None:
        return None
    dims = "x".join(f"{n}={s}" for n, s in
                    zip(mesh.axis_names, mesh.devices.shape))
    return f"{mesh_axis}@{dims}"


# neutral pins for undeclared knobs — the cache never splits on options
# an engine ignores
_NEUTRAL_OPTS = {"strip": 1, "tb_pack": 1, "xdrop": None}


def validate_int_option(name: str, value, *,
                        minimum: Optional[int] = None) -> int:
    """Validate a numeric option value, naming the offending option.

    Rejects non-integers (including bools and non-integral floats —
    ``int()`` would silently truncate ``strip=2.5`` to 2) so bad values
    fail at plan-key construction instead of surfacing as shape errors
    inside the fill.
    """
    if isinstance(value, bool) or not isinstance(value, (int, np.integer)):
        raise ValueError(
            f"option {name!r} must be an integer, got {value!r} "
            f"({type(value).__name__})")
    value = int(value)
    if minimum is not None and value < minimum:
        raise ValueError(
            f"option {name!r} must be >= {minimum}, got {value}")
    return value


def validate_pow2_option(name: str, value) -> int:
    """An integer option that must also be a power of two (block/bucket
    shaped knobs, e.g. the mapper's ``screen_block``)."""
    v = validate_int_option(name, value, minimum=1)
    if v & (v - 1):
        raise ValueError(
            f"option {name!r} must be a power of two, got {v}")
    return v


def resolve_engine_options(spec: T.DPKernelSpec, engine_name: str,
                           requested: Optional[dict] = None) -> dict:
    """Resolve every schedule knob an engine declares against a request.

    ``requested`` maps option name -> value; ``None`` values mean "use
    the engine default" — a per-backend dict (``{'cpu': ..., 'default':
    ...}``) resolves against ``jax.default_backend()``, and ``tb_pack``
    falls back to the kernel's natural packing ``spec.tb_pack``
    (8 // ptr_bits).  Option names the engine does not declare raise
    immediately, listing the valid choices — instead of surfacing as an
    unexpected-keyword TypeError deep inside the fill.  Undeclared knobs
    resolve to their neutral value so every PlanKey field is populated.
    """
    sup = registry.engine_options(engine_name)
    req = {k: v for k, v in dict(requested or {}).items() if v is not None}
    plan_knobs = {k for k, v in sup.items() if v != "dynamic"}
    unknown = sorted(set(req) - plan_knobs)
    if unknown:
        valid = sorted(plan_knobs)
        raise ValueError(
            f"engine {engine_name!r} does not accept option(s) {unknown}; "
            f"valid options: {valid if valid else '(none)'}")
    out = dict(_NEUTRAL_OPTS)
    for name in plan_knobs:
        default = sup[name]
        if name == "strip":
            strip = req.get("strip")
            if strip is None:
                strip = default
                if isinstance(strip, dict):
                    strip = strip.get(jax.default_backend(),
                                      strip["default"])
            out["strip"] = validate_int_option("strip", strip, minimum=1)
        elif name == "tb_pack":
            if spec.traceback is None:
                out["tb_pack"] = 1
                continue
            from repro.core.engine import resolve_tb_pack
            tb_pack = req.get("tb_pack")
            if tb_pack is None and default is not None:
                tb_pack = default
            if tb_pack is not None:
                tb_pack = validate_int_option("tb_pack", tb_pack)
            out["tb_pack"] = resolve_tb_pack(spec, tb_pack)  # one validator
        elif name == "xdrop":
            xdrop = req.get("xdrop", default)
            if xdrop is not None:
                xdrop = validate_int_option("xdrop", xdrop, minimum=0)
            out["xdrop"] = xdrop
        else:
            out[name] = req.get(name, default)
    return out


def resolve_engine_opts(spec: T.DPKernelSpec, engine_name: str,
                        strip: Optional[int] = None,
                        tb_pack: Optional[int] = None) -> tuple[int, int]:
    """Deprecated: the (strip, tb_pack) pair from
    :func:`resolve_engine_options` — call that instead (it returns every
    declared knob, validates names, and is what the plan cache uses)."""
    import warnings
    warnings.warn(
        "resolve_engine_opts is deprecated; use resolve_engine_options "
        "(returns the full resolved option dict)",
        DeprecationWarning, stacklevel=2)
    r = resolve_engine_options(spec, engine_name,
                               {"strip": strip, "tb_pack": tb_pack})
    return r["strip"], r["tb_pack"]


def _tuned_defaults(kernel: str, engine_name: str, bucket: tuple,
                    batch_size: Optional[int]) -> Optional[dict]:
    """Winning schedule options from the persisted autotuning table,
    consulted only when the caller passed no explicit option.  Any table
    problem (missing, corrupt, stale schema) falls back to the
    hand-picked defaults — a bad table must never break dispatch.  Only
    options the engine actually declares are forwarded, so a table
    written against a richer engine cannot poison resolution."""
    try:
        from repro.tune import table as tune_table
        with obs_trace.span("plan.tune_lookup", cat="plan", kernel=kernel,
                            engine=engine_name):
            tuned = tune_table.lookup(kernel, engine_name, bucket,
                                      batch_size)
    except Exception:
        obs_metrics.REGISTRY.counter("plan_tune_lookups_total",
                                     outcome="error").inc()
        return None
    obs_metrics.REGISTRY.counter(
        "plan_tune_lookups_total",
        outcome="hit" if tuned else "miss").inc()
    if not tuned:
        return None
    sup = registry.engine_options(engine_name)
    return {k: v for k, v in tuned.items()
            if v is not None and sup.get(k, "dynamic") != "dynamic"}


def lower_plan_hlo(spec: T.DPKernelSpec, params, engine_name: str,
                   q_shape: tuple, r_shape: tuple, *,
                   batch_size: Optional[int] = None,
                   with_traceback: bool = True, mode: str = "align",
                   strip: Optional[int] = None,
                   tb_pack: Optional[int] = None,
                   xdrop: Optional[int] = None) -> str:
    """Unoptimized HLO text of exactly the program :func:`get_plan`
    would compile for these arguments — lowered (traced) but *not*
    XLA-compiled, so the autotuner's cost model can rank schedule
    candidates without paying a compile per candidate.
    """
    wtb = bool(with_traceback and spec.traceback is not None)
    opts = resolve_engine_options(
        spec, engine_name,
        {"strip": strip, "tb_pack": tb_pack, "xdrop": xdrop})
    key = PlanKey(kernel=spec.name, engine=engine_name,
                  bucket_shape=(tuple(q_shape), tuple(r_shape)),
                  batch_size=batch_size, with_traceback=wtb, mode=mode,
                  strip=opts["strip"], tb_pack=opts["tb_pack"],
                  semiring=spec.semiring.name, xdrop=opts["xdrop"])
    fn = _build_fn(key, spec, engine_name)
    cdt = jnp.dtype(spec.char_dtype)
    if batch_size is None:
        q = jax.ShapeDtypeStruct(tuple(q_shape), cdt)
        r = jax.ShapeDtypeStruct(tuple(r_shape), cdt)
        ql = jax.ShapeDtypeStruct((), jnp.int32)
        rl = jax.ShapeDtypeStruct((), jnp.int32)
    else:
        q = jax.ShapeDtypeStruct((batch_size,) + tuple(q_shape), cdt)
        r = jax.ShapeDtypeStruct((batch_size,) + tuple(r_shape), cdt)
        ql = jax.ShapeDtypeStruct((batch_size,), jnp.int32)
        rl = jax.ShapeDtypeStruct((batch_size,), jnp.int32)
    lowered = jax.jit(fn).lower(params, q, r, ql, rl)
    return lowered.compiler_ir(dialect="hlo").as_hlo_text()



def traceback_bytes(spec: T.DPKernelSpec, q_bucket: int, r_bucket: int, *,
                    engine_name: str = "wavefront",
                    strip: Optional[int] = None,
                    tb_pack: Optional[int] = None) -> int:
    """Traceback-store bytes one alignment occupies at a bucket shape —
    the per-alignment HBM footprint that caps how many alignments a
    fixed memory budget can keep in flight (packed pointers cut it by
    ``tb_pack``).

    Layout-aware per engine: the wavefront 'diag' store is
    ⌈(Q+R)/strip⌉ * strip wavefront rows of ⌈(Q+1)/(4*tb_pack)⌉ int32
    words; the
    Pallas ('chunk', n_pe, tb_pack) store is ⌈Q/n_pe⌉ chunks of
    ⌈(n_pe+R-1)/(4*tb_pack)⌉ int32 words per lane (Q padded up to the
    lane strip)."""
    if spec.traceback is None:
        return 0
    r = resolve_engine_options(spec, engine_name,
                               {"strip": strip, "tb_pack": tb_pack})
    strip_r, pack_r = r["strip"], r["tb_pack"]
    if engine_name.startswith("pallas"):
        n_pe = PALLAS_N_PE
        n_chunks = -(-q_bucket // n_pe)
        n_groups = -(-(n_pe + r_bucket - 1) // (4 * pack_r))
        return n_chunks * n_groups * n_pe * 4
    n_rows = -(-(q_bucket + r_bucket) // strip_r) * strip_r
    return n_rows * (-(-(q_bucket + 1) // (4 * pack_r))) * 4


def get_plan(spec: T.DPKernelSpec, engine_name: str,
             q_shape: tuple, r_shape: tuple, *,
             batch_size: Optional[int] = None,
             with_traceback: bool = True, mode: str = "align",
             mesh=None,
             mesh_axis: str = "data", strip: Optional[int] = None,
             tb_pack: Optional[int] = None,
             xdrop: Optional[int] = None) -> CompiledPlan:
    """Fetch (or build) the shared plan for one bucketed input shape.

    ``q_shape``/``r_shape`` are per-pair shapes including char dims (the
    bucket shape); ``batch_size=None`` compiles the single-pair variant.
    With ``mesh`` the plan shards the batch axis over ``mesh_axis`` (the
    mesh itself joins the cache key — sharded and local serving share one
    substrate, but distinct meshes never share an executable).  The spec
    object itself keys the cache (two specs made by the same
    ``kernels_zoo.make`` call share; distinct constructions do not —
    their closures could differ).

    ``strip`` (anti-diagonals per scan step), ``tb_pack`` (pointers per
    traceback byte) and ``xdrop`` (X-drop early termination) select the
    engine schedule; ``None`` resolves the engine/kernel defaults
    (strip-mined, packed, no X-drop).  Passing a non-``None`` value for
    an option the engine does not declare raises, listing the valid
    choices.

    When *no* explicit option is passed, the persisted autotuning table
    (``repro.tune.table``, env ``REPRO_TUNE_TABLE``) is consulted first:
    a committed sweep's winning schedule for this (kernel, engine,
    bucket, batch, backend) replaces the hand-picked defaults.  Explicit
    options always win, and ``REPRO_TUNE_TABLE=off`` restores the
    hand-picked defaults exactly.
    """
    wtb = bool(with_traceback and spec.traceback is not None)
    requested = {"strip": strip, "tb_pack": tb_pack, "xdrop": xdrop}
    if all(v is None for v in requested.values()):
        tuned = _tuned_defaults(spec.name, engine_name,
                                (q_shape[0], r_shape[0]), batch_size)
        if tuned:
            requested.update(tuned)
    opts = resolve_engine_options(spec, engine_name, requested)
    strip_r, pack_r, xdrop_r = opts["strip"], opts["tb_pack"], opts["xdrop"]
    if mesh is None:
        mesh_axis = "data"   # axis is meaningless un-sharded; don't split
    cache_key = (spec, engine_name, tuple(q_shape), tuple(r_shape),
                 batch_size, wtb, mode, mesh, mesh_axis,
                 strip_r, pack_r, xdrop_r)
    plan = _CACHE.get(cache_key)
    if plan is not None:
        _STATS["hits"] += 1
        plan.hits += 1
        obs_metrics.REGISTRY.counter("plan_cache_hits_total").inc()
        return plan
    with _LOCK:
        plan = _CACHE.get(cache_key)
        if plan is None:
            _STATS["misses"] += 1
            obs_metrics.REGISTRY.counter("plan_cache_misses_total").inc()
            key = PlanKey(kernel=spec.name, engine=engine_name,
                          bucket_shape=(tuple(q_shape), tuple(r_shape)),
                          batch_size=batch_size, with_traceback=wtb,
                          mode=mode, placement=_placement(mesh, mesh_axis),
                          strip=strip_r, tb_pack=pack_r,
                          semiring=spec.semiring.name, xdrop=xdrop_r)
            plan = CompiledPlan(key, spec, engine_name, mesh=mesh,
                                mesh_axis=mesh_axis)
            _CACHE[cache_key] = plan
        else:
            _STATS["hits"] += 1
            plan.hits += 1
            obs_metrics.REGISTRY.counter("plan_cache_hits_total").inc()
    return plan


# measurement history of plans retired by clear_plan_cache(keep_stats=
# True): autotune sweeps clear compiled executables between configs
# without losing the session's compile-time/call accounting
_RETIRED = {"plans": 0, "calls": 0, "hits": 0,
            "compiled": 0, "compile_s": 0.0}


def _totals() -> dict[str, Any]:
    t = dict(_RETIRED)
    t["plans"] += len(_CACHE)
    for p in _CACHE.values():
        t["calls"] += p.calls
        t["hits"] += p.hits
        if p.compile_s is not None:
            t["compiled"] += 1
            t["compile_s"] += p.compile_s
    return t


def plan_cache_info() -> dict[str, Any]:
    """Cache-wide totals plus per-plan observability: each entry of
    ``plans`` carries the PlanKey, its cache ``hits`` (after the initial
    miss), dispatch ``calls``, and first-call ``compile_s``.

    ``totals`` rolls calls/hits/compile counts and compile seconds up
    across live plans *and* plans retired by
    ``clear_plan_cache(keep_stats=True)`` — the session-wide measurement
    history an autotune sweep or a warm-boot report reads."""
    plans = [{"key": p.key, "hits": p.hits, "calls": p.calls,
              "compile_s": p.compile_s} for p in _CACHE.values()]
    return {"size": len(_CACHE), "hits": _STATS["hits"],
            "misses": _STATS["misses"],
            "keys": [p.key for p in _CACHE.values()],
            "plans": plans, "totals": _totals(),
            "compile_ledger": obs_metrics.compile_ledger_snapshot()}


def clear_plan_cache(keep_stats: bool = False) -> None:
    """Drop every compiled plan.  ``keep_stats=True`` rolls the retired
    plans' hit/call/compile_s counters into ``plan_cache_info()
    ['totals']`` (and keeps the cache-wide hit/miss counters) so a sweep
    can clear executables without losing measurement history."""
    with _LOCK:
        if keep_stats:
            for p in _CACHE.values():
                _RETIRED["plans"] += 1
                _RETIRED["calls"] += p.calls
                _RETIRED["hits"] += p.hits
                if p.compile_s is not None:
                    _RETIRED["compiled"] += 1
                    _RETIRED["compile_s"] += p.compile_s
                # per-key attribution survives the fold via the ledger
                obs_metrics.COMPILE_LEDGER.update_usage(
                    plan_key_str(p.key), p.calls, p.hits)
        else:
            _STATS["hits"] = _STATS["misses"] = 0
            _RETIRED.update(plans=0, calls=0, hits=0,
                            compiled=0, compile_s=0.0)
            obs_metrics.COMPILE_LEDGER.clear()
        _CACHE.clear()
