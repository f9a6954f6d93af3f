"""The paper's accelerator as a service: batched DP alignment over a mesh.

This is the N_K x N_B arbiter of DP-HLS §5.3 at pod scale: requests queue
up per ``(kernel, length-bucket)`` channel (heterogeneous kernels =
multiple channels, exactly the paper's "mix of global and local
aligners"), are padded to their *bucket* — not a global ``max_len`` — and
dispatched through the shared ``repro.runtime`` compiled-plan cache
(sharded plans over the mesh 'data' axis live in the same cache: N_K
channels).  A 40-base query therefore pays the wavefront cost of a
64-cell bucket, not of the service-wide maximum.

The queue/admission/dispatch machinery lives in
:class:`repro.serve.gateway.Gateway`; this module contributes only what
is alignment-specific — the per-kernel :class:`~repro.serve.gateway.Channel`
(bucketing, padding, the opt-in ``myers`` prefilter rung, plan
resolution, result landing) and the service facade.  Everything the
gateway provides comes with it: pipelined multi-batch dispatch
(``pipeline_depth``), heartbeat-driven redispatch, generation counters
against double-completion, ``max_pending`` backpressure
(block/raise/shed), bounded retries with a dead-letter queue, deadlines,
fault injection (``fault_plan``), the multi-worker ``serve()`` pool, and
overload degradation to the bit-parallel edit-distance screen
(``degrade='myers'``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import batch as core_batch, kernels_zoo
from repro.core.kernels_zoo import edit as edit_kernel
from repro.core.traceback import block_cigars, raise_if_truncated
from repro.obs import trace as obs_trace
from repro.runtime import bucketing
from repro.runtime import plan as plan_mod

from . import gateway as gateway_mod
from .gateway import (FaultPlan, Gateway, InflightBatch, ServiceOverloaded,
                      ShedOverload)

__all__ = ["AlignRequest", "AlignFuture", "AlignmentService",
           "InflightBatch", "ServiceOverloaded"]


@dataclasses.dataclass(eq=False)   # identity semantics: ndarray fields
class AlignRequest:
    rid: int
    kernel: str                  # kernels_zoo name
    query: np.ndarray
    ref: np.ndarray
    result: Optional[dict] = None
    gen: int = 0                 # bumped on every re-submission
    waits: int = 0               # batch pops this request was passed over
    attempts: int = 0            # failed dispatches (bounded-retry budget)
    not_before: float = 0.0      # retry backoff gate
    deadline: Optional[float] = None


class AlignFuture:
    """Lightweight handle returned by ``submit``; resolving it drives the
    service's dispatcher loop (single-process: there is no background
    thread — ``result()`` pumps ``wait`` until this request completes).
    A dead-lettered request resolves with the typed error dict
    (``result()["failed"]``) instead of hanging."""

    __slots__ = ("req", "_svc")

    def __init__(self, req: AlignRequest, svc: "AlignmentService"):
        self.req = req
        self._svc = svc

    def done(self) -> bool:
        return self.req.result is not None

    def result(self, worker: str = "w0") -> dict:
        if not self.done():
            self._svc.wait([self], worker=worker)
        if self.req.result is None:
            raise RuntimeError(f"request {self.req.rid} did not complete")
        return self.req.result

    def __repr__(self):
        state = "done" if self.done() else "pending"
        return f"AlignFuture(rid={self.req.rid}, {state})"


QueueKey = Tuple[str, Tuple[int, int]]   # (kernel, (q_bucket, r_bucket))

# serving-side filter ladder: one module-level screen spec so every
# prefilter batch lands on the same plan-cache keys
_PREFILTER_SPEC = edit_kernel.edit_search()


class _AlignChannel(gateway_mod.Channel):
    """One kernel's channel: queue keys stay ``(kernel, bucket)`` and the
    dispatch record keeps its historical shape."""

    def __init__(self, svc: "AlignmentService", kernel: str):
        self.svc = svc
        self.name = kernel

    def bucket_of(self, job: AlignRequest) -> Tuple[int, int]:
        return self.svc._bucket(job)

    def job_len(self, job: AlignRequest) -> int:
        return len(job.query) + len(job.ref)

    def job_cells(self, job: AlignRequest) -> int:
        return len(job.query) * len(job.ref)

    def block_for(self, bucket) -> int:
        return self.svc.block_for(self.name, bucket)

    def coalesce(self, bucket, jobs, block):
        svc = self.svc
        if not svc.coalesce:
            return bucket, block, False
        grown = svc._coalesce_batch(self.name, bucket, jobs, block)
        if grown == bucket:
            return bucket, block, False
        # re-cap the pad rows at the grown bucket
        return grown, max(len(jobs),
                          min(block, self.block_for(grown))), True

    def launch(self, bucket, reqs, block):
        svc = self.svc
        spec, params, sharded_fn = svc._channel(self.name)
        with obs_trace.span("gw.pad", cat="gateway", n=len(reqs)):
            t_pad = time.monotonic()
            qs, rs, ql, rl = svc._pad_batch(
                reqs, bucket, spec.char_shape,
                np.dtype(jnp.dtype(spec.char_dtype).name), block)
            svc._metrics.counter("gw_host_seconds_total", phase="pad").inc(
                time.monotonic() - t_pad)
        if svc._screenable(spec):
            # ladder rung 1: rejects resolve here; only survivors
            # (rebound into ``reqs`` so a failing main launch requeues
            # exactly the requests still owed a result) pay the full
            # plan below
            reqs, qs, rs, ql, rl = svc._prefilter_batch(
                spec, reqs, bucket, qs, rs, ql, rl, block)
            if not reqs:
                return [], None
        if sharded_fn is not None:
            out = sharded_fn(params, jnp.asarray(qs), jnp.asarray(rs),
                             jnp.asarray(ql), jnp.asarray(rl))
        else:
            plan = plan_mod.get_plan(
                spec, svc.engine_name, qs.shape[1:], rs.shape[1:],
                batch_size=block,
                with_traceback=svc.with_traceback and
                spec.traceback is not None)
            out = plan(params, jnp.asarray(qs), jnp.asarray(rs),
                       jnp.asarray(ql), jnp.asarray(rl))
        return reqs, out

    def materialize(self, out):
        score = np.asarray(out.score)
        end_i = np.asarray(out.end_i)
        end_j = np.asarray(out.end_j)
        moves = n_moves = None
        if getattr(out, "moves", None) is not None:
            raise_if_truncated(out)      # never emit a corrupt path
            moves = np.asarray(out.moves)
            n_moves = np.asarray(out.n_moves)
        return score, end_i, end_j, moves, n_moves

    def prepare_landing(self, host, n):
        """The batch's first ``n`` rows (the pad rows are left out) as
        Python lists, with every row's CIGAR from one block-wide pass
        (span ``gw.cigar``, ``n``: rows encoded)."""
        score, end_i, end_j, moves, n_moves = host
        cigars = None
        if moves is not None:
            with obs_trace.span("gw.cigar", cat="gateway", channel=self.name,
                                n=n):
                cigars = block_cigars(moves, n_moves, rows=n)
        return (score[:n].astype(np.float64).tolist(),
                end_i[:n].astype(np.int64).tolist(),
                end_j[:n].astype(np.int64).tolist(), cigars)

    def land(self, job: AlignRequest, i: int, host) -> int:
        score, end_i, end_j, cigars = host
        res = {"score": score[i], "end": (end_i[i], end_j[i])}
        if cigars is not None:
            res["cigar"] = cigars[i]
        job.result = res
        return 1

    def record(self, bucket, n, coalesced):
        return {"kernel": self.name, "bucket": bucket, "n": n,
                "coalesced": coalesced}

    # -- overload degradation: answer with the myers screen ------------------
    @property
    def can_degrade(self) -> bool:
        svc = self.svc
        if svc.degrade != "myers":
            return False
        spec, _, _ = svc._channel(self.name)
        return (spec.char_shape == ()
                and np.dtype(jnp.dtype(spec.char_dtype).name) == np.uint8)

    def launch_degraded(self, bucket, reqs, block) -> None:
        """Past the degrade watermark, answer the whole batch with the
        bit-parallel edit-distance screen (exact distance: the threshold
        is set beyond the bucket perimeter so it never clips).  Degraded
        results are typed (``degraded: True``, ``score = -distance``) so
        callers can tell an approximation from a full alignment."""
        svc = self.svc
        spec, _, _ = svc._channel(self.name)
        qs, rs, ql, rl = svc._pad_batch(
            reqs, bucket, spec.char_shape,
            np.dtype(jnp.dtype(spec.char_dtype).name), block)
        params = edit_kernel.default_params(bucket[0] + bucket[1])
        screen = plan_mod.get_plan(
            _PREFILTER_SPEC, svc.prefilter_engine,
            qs.shape[1:], rs.shape[1:], batch_size=block,
            with_traceback=False, mode="fill")
        out = screen(params, jnp.asarray(qs), jnp.asarray(rs),
                     jnp.asarray(ql), jnp.asarray(rl))
        dist = np.asarray(out.score)[: len(reqs)]
        for r, d in zip(reqs, dist):
            if r.result is not None:
                continue
            r.result = {"score": -float(d), "edit_distance": int(d),
                        "end": (0, 0), "degraded": True}
            svc._job_resolved(r, 1, "degraded")


class AlignmentService(Gateway):
    """Alignment channels on the unified gateway.

    ``mesh=None`` runs un-sharded (CPU smoke); with a mesh, each kernel
    channel resolves a sharded plan over the 'data' axis — both paths go
    through the runtime plan cache.  ``max_len`` caps request lengths
    (the largest bucket is ``max_len`` snapped up to the bucket grid);
    ``min_bucket`` floors the smallest.  ``pipeline_depth`` is how many
    batches may be in flight on the device at once (1 = synchronous).

    ``tb_budget_bytes`` sizes batches by memory instead of the fixed
    ``block``: each (kernel, bucket) channel launches as many alignments
    as fit the traceback-store budget (never fewer than ``block``, at
    most ``max_block``).  Bit-packed pointers cut the per-alignment
    footprint by the kernel's ``tb_pack``, so the same budget admits up
    to 4x larger blocks — the serving-side payoff of the packed store.

    ``max_pending`` bounds how many submitted-but-incomplete requests
    the service holds (queued + in flight); ``backpressure`` picks what
    ``submit`` does at the budget: ``'block'`` synchronously works one
    batch at a time off the queues until there is room (the producer is
    slowed to the service's pace), ``'raise'`` sheds the request with
    :class:`ServiceOverloaded` (the caller owns retry policy), and
    ``'shed'`` resolves the newest request immediately with a typed
    ``shed`` error result.  The budget bounds host memory *and*
    worst-case result latency — an unbounded intake queue hides, rather
    than signals, an overloaded service.

    The robustness knobs (``fault_plan``, ``max_retries``,
    ``retry_backoff_s``, ``deadline_s``, ``harvest_timeout_s``,
    ``degrade``/``degrade_watermark``) and the multi-worker ``serve()``
    pool are inherited from :class:`~repro.serve.gateway.Gateway`.
    """

    def __init__(self, max_len: int = 256, block: int = 8, mesh=None,
                 engine_name: str = "wavefront", with_traceback: bool = True,
                 redispatch_after: float = 60.0,
                 min_bucket: int = bucketing.DEFAULT_MIN_BUCKET,
                 coalesce: bool = True, pipeline_depth: int = 2,
                 tb_budget_bytes: Optional[int] = None, max_block: int = 256,
                 max_pending: Optional[int] = None,
                 backpressure: str = "block",
                 prefilter: Optional[float] = None,
                 prefilter_engine: str = "myers",
                 warm_start: Optional[Sequence] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 max_retries: Optional[int] = 3,
                 retry_backoff_s: float = 0.0,
                 deadline_s: Optional[float] = None,
                 harvest_timeout_s: Optional[float] = None,
                 degrade: Optional[str] = None,
                 degrade_watermark: Optional[int] = None):
        Gateway.__init__(
            self, pipeline_depth=pipeline_depth, max_pending=max_pending,
            backpressure=backpressure, redispatch_after=redispatch_after,
            fault_plan=fault_plan, max_retries=max_retries,
            retry_backoff_s=retry_backoff_s, deadline_s=deadline_s,
            harvest_timeout_s=harvest_timeout_s,
            degrade_watermark=degrade_watermark)
        self.max_len, self.block = max_len, block
        self.tb_budget_bytes = tb_budget_bytes
        self.max_block = max_block
        self.min_bucket = min(min_bucket, max_len)
        # largest admissible bucket: max_len snapped *up* to the grid, so
        # every request <= max_len has an on-grid bucket (an off-grid cap
        # must never become a compiled shape)
        self.max_bucket = bucketing.bucket_length(
            max_len, min_bucket=self.min_bucket)
        self.coalesce = coalesce
        self.mesh = mesh
        self.engine_name = engine_name
        self.with_traceback = with_traceback
        # filter ladder (opt-in): ``prefilter=frac`` screens every batch
        # with the thresholded bit-parallel edit_search before the main
        # plan — requests whose best edit distance exceeds
        # ceil(frac * query_len) resolve immediately with
        # ``{'filtered': True}`` and never pay full DP.  Only uint8
        # scalar-code channels are screened; None = no behavior change.
        if prefilter is not None and not 0.0 < prefilter < 1.0:
            raise ValueError(
                f"prefilter must be a fraction in (0, 1), got {prefilter}")
        self.prefilter = prefilter
        self.prefilter_engine = prefilter_engine
        if degrade not in (None, "myers"):
            raise ValueError(
                f"degrade must be None or 'myers', got {degrade!r}")
        self.degrade = degrade
        self.channels: Dict[str, tuple] = {}   # kernel -> (spec, params, fn)
        # AOT warm boot: pre-compile the declared channel grid so the
        # first request at each (kernel, bucket) lands on a hot plan
        if warm_start:
            self.warm(warm_start)

    def warm(self, entries: Sequence) -> int:
        """Pre-compile plans for ``(kernel, bucket)`` (or ``(kernel,
        bucket, block)``) channel entries; ``bucket`` may be one length
        (square) or a ``(q, r)`` pair, snapped to the service's bucket
        grid exactly as a request of those lengths would be.

        Each entry warms the same plan ``_launch`` would resolve —
        identical ``get_plan`` arguments, including the
        tuned-table default consultation — plus, on screenable channels,
        the prefilter's score-only screen plan.  Sharded channels
        (``mesh`` set) compile through ``core.batch`` lazily and are
        skipped.  Returns the number of plans warmed.
        """
        from repro.tune import warm as warm_mod

        n = 0
        for entry in entries:
            kernel, bucket = entry[0], entry[1]
            block = entry[2] if len(entry) > 2 else None
            if isinstance(bucket, int):
                bucket = (bucket, bucket)
            bucket = bucketing.bucket_shape(
                bucket[0], bucket[1], min_bucket=self.min_bucket,
                max_bucket=self.max_bucket)
            spec, params, sharded_fn = self._channel(kernel)
            if sharded_fn is not None:
                continue
            if block is None:
                block = self.block_for(kernel, bucket)
            char = spec.char_shape
            q_shape, r_shape = (bucket[0],) + char, (bucket[1],) + char
            if self._screenable(spec):
                warm_mod.warm_plan(
                    _PREFILTER_SPEC, edit_kernel.default_params(1),
                    self.prefilter_engine, q_shape, r_shape,
                    batch_size=block, with_traceback=False, mode="fill")
                n += 1
            warm_mod.warm_plan(
                spec, params, self.engine_name, q_shape, r_shape,
                batch_size=block,
                with_traceback=self.with_traceback and
                spec.traceback is not None)
            n += 1
        return n

    def _bucket(self, req: AlignRequest) -> Tuple[int, int]:
        return bucketing.bucket_shape(
            len(req.query), len(req.ref),
            min_bucket=self.min_bucket, max_bucket=self.max_bucket)

    def block_for(self, kernel: str, bucket: Tuple[int, int]) -> int:
        """Batch rows one launch carries at this (kernel, bucket) channel.

        Without a budget this is the fixed ``block``.  With
        ``tb_budget_bytes`` it is how many alignments' traceback stores
        fit the budget (floored at ``block``, capped at ``max_block``) —
        a 4x-packed kernel gets 4x the in-flight alignments per bucket.
        """
        if self.tb_budget_bytes is None:
            return self._mesh_rounded(self.block)
        spec, _, _ = self._channel(kernel)
        per = plan_mod.traceback_bytes(spec, bucket[0], bucket[1],
                                       engine_name=self.engine_name)
        if per == 0:                      # score-only kernel: no tb store
            return self._mesh_rounded(self.max_block)
        return self._mesh_rounded(
            max(self.block, min(self.max_block,
                                self.tb_budget_bytes // per)))

    def _mesh_rounded(self, block: int) -> int:
        """Sharded plans partition the batch axis over the mesh 'data'
        axis: round the block down to a divisible size (never below one
        row per device) so a budget-derived count can't break the
        sharding."""
        if self.mesh is None:
            return block
        n = int(dict(zip(self.mesh.axis_names,
                         self.mesh.devices.shape)).get("data", 1))
        return max(n, block // n * n)

    def _channel(self, kernel: str):
        """Per-kernel spec/params (+ sharded aligner when on a mesh)."""
        if kernel not in self.channels:
            with self._lock:
                if kernel not in self.channels:
                    spec, params = kernels_zoo.make(kernel)
                    fn = None
                    if self.mesh is not None:
                        fn = core_batch.make_sharded_aligner(
                            spec, self.mesh, engine_name=self.engine_name,
                            with_traceback=self.with_traceback and
                            spec.traceback is not None)
                    self.channels[kernel] = (spec, params, fn)
        return self.channels[kernel]

    def _resolve_channel(self, name: str) -> _AlignChannel:
        ch = self._gw_channels.get(name)
        if ch is None:
            with self._lock:
                ch = self._gw_channels.get(name)
                if ch is None:
                    ch = self.register_channel(_AlignChannel(self, name))
        return ch

    # -- intake ------------------------------------------------------------
    def _enqueue(self, req: AlignRequest) -> None:
        with self._lock:
            self._push(self._resolve_channel(req.kernel), req)

    def submit(self, req: AlignRequest) -> AlignFuture:
        if len(req.query) > self.max_len or len(req.ref) > self.max_len:
            raise ValueError(
                f"request {req.rid}: lengths ({len(req.query)}, "
                f"{len(req.ref)}) exceed max_len {self.max_len}")
        if not self._admit(req.rid):
            self._count_submitted(req)
            with self._lock:     # shed: resolve newest with a typed error
                self._dead_letter(
                    self._resolve_channel(req.kernel), req,
                    ShedOverload(
                        f"request {req.rid}: {self._pending} requests "
                        f"pending >= max_pending {self.max_pending}"),
                    free_pending=False, worker="submit")
            return AlignFuture(req, self)
        self._count_submitted(req)
        self._stamp_deadline(req)
        with self._lock:
            self._pending += 1
            self._push(self._resolve_channel(req.kernel), req)
        return AlignFuture(req, self)

    # -- batch formation ---------------------------------------------------
    def _pad_batch(self, reqs: List[AlignRequest], bucket: Tuple[int, int],
                   char_shape, dtype, n: int):
        Lq, Lr = bucket
        qs = np.zeros((n, Lq) + char_shape, dtype)
        rs = np.zeros((n, Lr) + char_shape, dtype)
        ql = np.zeros((n,), np.int32)
        rl = np.zeros((n,), np.int32)
        for i, r in enumerate(reqs):
            ql[i] = len(r.query)
            rl[i] = len(r.ref)
            qs[i, : ql[i]] = r.query
            rs[i, : rl[i]] = r.ref
        # pad rows beyond the request count with length-1 dummies
        ql[len(reqs):] = 1
        rl[len(reqs):] = 1
        return qs, rs, ql, rl

    def _coalesce_batch(self, kernel: str, bucket: Tuple[int, int],
                        reqs: List[AlignRequest], block: int) -> Tuple[int, int]:
        """Top a partial batch up with requests from dominating buckets.

        A bucket ``b2`` dominates when both sides are >= ``bucket`` — its
        requests fit after padding to ``b2``, so the combined batch
        dispatches at the elementwise-max bucket.  Only a donor that
        cannot fill its own block gives: a small request must never take
        the row of one that would have launched in a full block.
        Closest (smallest dominating) buckets are drained first to keep
        padding waste low.  Under a memory budget the row cap is
        re-evaluated at each grown bucket (``block_for``), so coalescing
        into a bigger bucket can never launch a batch whose traceback
        store exceeds the budget.
        """
        out_bucket = bucket
        donors = sorted(
            (b2 for (k2, b2), queue in self.queues.items()
             if k2 == kernel and b2 != bucket
             and b2[0] >= bucket[0] and b2[1] >= bucket[1]
             and 0 < len(queue) < self.block_for(kernel, b2)),
            key=lambda b2: b2[0] * b2[1])
        for b2 in donors:
            grown = (max(out_bucket[0], b2[0]), max(out_bucket[1], b2[1]))
            allowed = min(block, self.block_for(kernel, grown))
            if len(reqs) >= allowed:
                break                 # growing further would bust the cap
            queue = self.queues[(kernel, b2)]
            while queue and len(reqs) < allowed:
                reqs.append(queue.pop(0))
                out_bucket = grown
            if len(reqs) >= allowed:
                break
        return out_bucket

    # -- the prefilter rung ------------------------------------------------
    def _screenable(self, spec) -> bool:
        """The edit screen only reads uint8 scalar symbol codes; channels
        with per-position channels (profiles, DTW floats) pass through."""
        return (self.prefilter is not None and spec.char_shape == ()
                and np.dtype(jnp.dtype(spec.char_dtype).name) == np.uint8)

    def _prefilter_batch(self, spec, reqs, bucket, qs, rs, ql, rl, block):
        """Screen one padded batch with thresholded bit-parallel
        edit_search; rejects resolve immediately with ``filtered: True``
        and the channel-sentinel score.  One engine-side threshold (the
        batch max) keeps a single screen plan per bucket; the exact
        per-request cut ``ceil(prefilter * query_len)`` applies host-side.
        """
        ks = [int(np.ceil(self.prefilter * len(r.query))) for r in reqs]
        params = edit_kernel.default_params(max(ks))
        screen = plan_mod.get_plan(
            _PREFILTER_SPEC, self.prefilter_engine,
            qs.shape[1:], rs.shape[1:], batch_size=block,
            with_traceback=False, mode="fill")
        out = screen(params, jnp.asarray(qs), jnp.asarray(rs),
                     jnp.asarray(ql), jnp.asarray(rl))
        dist = np.asarray(out.score)[: len(reqs)]   # sync: screen is cheap
        sent = float(spec.sentinel())
        survivors = []
        for r, d, k in zip(reqs, dist, ks):
            if float(d) <= k:
                survivors.append(r)
            else:
                r.result = {"score": sent, "end": (0, 0), "filtered": True}
                self._job_resolved(r, 1, "filtered")
        if len(survivors) != len(reqs):
            qs, rs, ql, rl = self._pad_batch(survivors, bucket,
                                             spec.char_shape, qs.dtype,
                                             block)
        return survivors, qs, rs, ql, rl
