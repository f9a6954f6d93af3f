"""Gateway robustness: fault injection, retries, deadlines, shedding,
degradation, and multi-worker recovery.

The alignment and genotyping services are used as the concrete gateways
(they are thin channels over ``serve.gateway.Gateway``); the invariants
under test are the gateway's: deterministic FaultPlan decisions, bounded
retries ending in typed dead letters, deadline expiry, newest-first
shedding, degrade-to-myers answers, kill-then-recover with zero
double completions, and the launch order (fullest block first, bounded
passes over any queue).
"""
from __future__ import annotations

import collections
import dataclasses

import numpy as np
import pytest

from repro.obs import trace as obs_trace
from repro.serve import (AlignRequest, AlignmentService, Channel, FaultPlan,
                         Gateway, GenotypeRequest, GenotypingService,
                         InjectedFault, WorkerKilled)


def _req(rid, rng, n=12, kernel="global_affine"):
    return AlignRequest(rid=rid, kernel=kernel,
                        query=rng.integers(0, 4, n).astype(np.uint8),
                        ref=rng.integers(0, 4, n + 2).astype(np.uint8))


# -- FaultPlan determinism ---------------------------------------------------
def test_fault_plan_is_deterministic():
    a = FaultPlan(seed=7, fail_launch_p=0.5, fail_harvest_p=0.5,
                  latency_s=0.1, latency_p=0.5)
    b = FaultPlan(seed=7, fail_launch_p=0.5, fail_harvest_p=0.5,
                  latency_s=0.1, latency_p=0.5)
    for w in ("w0", "w1"):
        for s in range(32):
            assert a.fails_launch(w, s) == b.fails_launch(w, s)
            assert a.fails_harvest(w, s) == b.fails_harvest(w, s)
            assert a.harvest_latency(w, s) == b.harvest_latency(w, s)
    # decisions are per-(worker, seq, site): the same seq draws
    # independently for launch vs harvest and across workers
    draws = {a.fails_launch("w0", s) for s in range(64)}
    assert draws == {True, False}
    c = FaultPlan(seed=8, fail_launch_p=0.5)
    assert any(a.fails_launch("w0", s) != c.fails_launch("w0", s)
               for s in range(64))


def test_fault_plan_kill_schedule():
    fp = FaultPlan(kill={"w0": 3, "w1": (1, 4)})
    assert fp.kills("w0", 3) and not fp.kills("w0", 2)
    assert fp.kills("w1", 1) and fp.kills("w1", 4) and not fp.kills("w1", 2)
    assert not fp.kills("w9", 0)


# -- bounded retries + dead letters ------------------------------------------
def test_bounded_retries_dead_letter_align(rng):
    svc = AlignmentService(max_len=32, block=2, max_retries=1,
                           fault_plan=FaultPlan(seed=1, fail_launch_p=1.0))
    fut = svc.submit(_req(0, rng))
    # attempt 1 requeues, attempt 2 exceeds max_retries=1 -> dead letter
    for _ in range(2):
        with pytest.raises(InjectedFault):
            svc.drain()
    assert fut.done()
    res = fut.result()
    assert res["failed"] and res["error"]["kind"] == "retries"
    assert svc._pending == 0
    assert len(svc.dead_letters) == 1
    assert svc.dead_letters[0]["rid"] == 0
    assert svc.dead_letters[0]["kind"] == "retries"
    assert svc.stats["retries"] == 1
    assert svc.drain() == 0          # nothing left: no retry-forever spin


def test_bounded_retries_dead_letter_genotyping():
    svc = GenotypingService(max_len=32, block=8, max_retries=0,
                            fault_plan=FaultPlan(seed=2, fail_launch_p=1.0))
    fut = svc.submit(GenotypeRequest(
        rid=5, reads=[np.ones(8, np.uint8)] * 2,
        haplotypes=[np.ones(8, np.uint8)] * 2))
    with pytest.raises(InjectedFault):
        svc.drain()
    # the whole site fails once (one typed result, one dead letter),
    # not once per pair job
    res = fut.result()
    assert res["failed"] and res["error"]["kind"] == "retries"
    assert len(svc.dead_letters) == 1
    assert svc._pending == 0
    # sibling pair jobs of the failed site are dropped, not dispatched
    assert svc.drain() == 0


def test_retry_backoff_gates_requeue(rng, monkeypatch):
    from repro.runtime import plan as plan_mod
    svc = AlignmentService(max_len=32, block=2, max_retries=5,
                           retry_backoff_s=10.0)
    t = {"now": 0.0}
    svc._clock = lambda: t["now"]
    req = _req(0, rng)
    svc.submit(req)
    real_get_plan = plan_mod.get_plan
    boom = {"armed": True}

    def failing_get_plan(*a, **kw):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("transient")
        return real_get_plan(*a, **kw)

    monkeypatch.setattr(plan_mod, "get_plan", failing_get_plan)
    with pytest.raises(RuntimeError, match="transient"):
        svc.drain()
    assert req.attempts == 1
    assert req.not_before == pytest.approx(10.0)   # 10 * 2**0
    assert svc.drain() == 0          # cooling down: nothing dispatched
    assert req.result is None
    t["now"] = 11.0
    assert svc.drain() == 1          # backoff elapsed -> retried fine
    assert req.result is not None and "score" in req.result


# -- deadlines ---------------------------------------------------------------
def test_deadline_dead_letters_on_dispatch(rng):
    svc = AlignmentService(max_len=32, block=2, deadline_s=5.0)
    t = {"now": 0.0}
    svc._clock = lambda: t["now"]
    fut = svc.submit(_req(0, rng))
    assert fut.req.deadline == pytest.approx(5.0)
    t["now"] = 10.0
    assert svc.drain() == 0          # expired before dispatch
    res = fut.result()
    assert res["failed"] and res["error"]["kind"] == "deadline"
    assert svc._pending == 0
    assert svc.dead_letters and svc.dead_letters[0]["kind"] == "deadline"


def test_deadline_sweep_on_idle_queue(rng):
    svc = AlignmentService(max_len=32, block=2, deadline_s=2.0)
    t = {"now": 0.0}
    svc._clock = lambda: t["now"]
    futs = [svc.submit(_req(i, rng)) for i in range(3)]
    assert svc.sweep_deadlines() == 0
    t["now"] = 3.0
    assert svc.sweep_deadlines() == 3
    assert all(f.result()["error"]["kind"] == "deadline" for f in futs)
    assert svc._pending == 0


def test_harvest_timeout_reclaims_batch(rng):
    svc = AlignmentService(max_len=32, block=2, harvest_timeout_s=5.0)
    t = {"now": 0.0}
    svc._clock = lambda: t["now"]
    req = _req(0, rng)
    svc.submit(req)
    item = svc._next_batch()
    svc._launch("w_wedged", item)    # launched_at = 0.0
    assert svc.redispatch_timed_out() == 0
    t["now"] = 6.0
    assert svc.redispatch_timed_out() == 1
    assert req.gen == 1 and req.attempts == 1
    assert svc.inflight == {}
    assert svc.drain(worker="w_ok") == 1      # requeued copy completes
    assert req.result is not None


# -- overload: shed + degrade ------------------------------------------------
def test_backpressure_shed_rejects_newest(rng):
    svc = AlignmentService(max_len=32, block=2, max_pending=2,
                           backpressure="shed")
    f0 = svc.submit(_req(0, rng))
    f1 = svc.submit(_req(1, rng))
    f2 = svc.submit(_req(2, rng))             # past budget: shed
    assert f2.done() and f2.result()["error"]["kind"] == "shed"
    assert not f0.done() and not f1.done()
    assert svc._pending == 2
    assert svc.stats["shed"] == 1
    assert svc.drain() == 2                   # admitted requests unaffected
    assert "score" in f0.result() and "score" in f1.result()


def test_degrade_to_myers_past_watermark(rng):
    svc = AlignmentService(max_len=32, block=4, degrade="myers",
                           degrade_watermark=3, coalesce=False)
    q = rng.integers(0, 4, 12).astype(np.uint8)
    futs = [svc.submit(AlignRequest(rid=i, kernel="global_affine",
                                    query=q, ref=q))
            for i in range(4)]                # pending 4 >= watermark 3
    assert svc.drain() == 0                   # all answered approximately
    for f in futs:
        res = f.result()
        assert res["degraded"] is True
        assert res["edit_distance"] == 0      # identical sequences
        assert res["score"] == 0.0
    assert svc._pending == 0
    assert svc.stats["degraded"] == 4
    assert any(d.get("degraded") for d in svc.dispatches)


def test_degrade_off_below_watermark(rng):
    svc = AlignmentService(max_len=32, block=4, degrade="myers",
                           degrade_watermark=100)
    fut = svc.submit(_req(0, rng))
    svc.drain()
    assert "degraded" not in fut.result() and "score" in fut.result()


# -- kill + recovery ---------------------------------------------------------
def test_worker_kill_leaves_window_for_heartbeat_reclaim(rng):
    import time as time_mod
    svc = AlignmentService(max_len=32, block=2, pipeline_depth=2,
                           coalesce=False, redispatch_after=5.0,
                           fault_plan=FaultPlan(kill={"w0": 1}))
    reqs = [_req(i, rng) for i in range(6)]
    futs = [svc.submit(r) for r in reqs]
    with pytest.raises(WorkerKilled):
        svc.drain(worker="w0")
    # dispatch #0 launched and stays in flight (silent death: no
    # cleanup); dispatch #1's jobs were requeued before the kill
    assert "w0" in svc.inflight and len(svc.inflight["w0"]) == 1
    assert svc.stats["killed"] == [{"worker": "w0", "seq": 1}]
    # the heartbeat deadline reclaims the stranded batch
    reclaimed = svc.redispatch_dead(now=time_mod.time() + 1000.0)
    assert reclaimed == 2
    assert svc.inflight == {}
    # a healthy worker finishes everything, exactly once per request
    assert svc.drain(worker="w1") == 6
    assert all(f.done() for f in futs)
    assert svc.stats["completed"] == 6 and svc._pending == 0


def test_serve_pool_completes_and_matches_inline(rng):
    """The multi-worker pool produces the same per-request results as
    the inline single-worker drain."""
    base = [_req(i, rng, n=8 + (i % 5) * 4) for i in range(24)]

    ref_svc = AlignmentService(max_len=64, block=4, coalesce=False)
    ref = [AlignRequest(rid=r.rid, kernel=r.kernel, query=r.query,
                        ref=r.ref) for r in base]
    for r in ref:
        ref_svc.submit(r)
    ref_svc.drain()

    svc = AlignmentService(max_len=64, block=4, coalesce=False)
    for r in base:
        svc.submit(r)
    stats = svc.serve(n_workers=3, timeout_s=120.0)
    assert stats["completed"] == 24
    assert svc._pending == 0 and svc.inflight == {}
    assert [r.result for r in base] == [r.result for r in ref]


def test_serve_elastic_respawns_killed_worker(rng):
    svc = AlignmentService(max_len=64, block=2, coalesce=False,
                           redispatch_after=0.5,
                           fault_plan=FaultPlan(kill={"w0": 0}))
    # warm the one (kernel, bucket) shape: with a 0.5s heartbeat
    # deadline, a cold multi-second compile inside launch would read as
    # a dead worker and charge spurious retry attempts
    svc.warm([("global_affine", (12, 14))])
    futs = [svc.submit(_req(i, rng)) for i in range(12)]
    stats = svc.serve(n_workers=2, timeout_s=120.0, elastic=True,
                      max_workers=4)
    assert all(f.done() for f in futs)
    assert all("score" in f.result() for f in futs)
    assert stats["killed"] and stats["killed"][0]["worker"] == "w0"
    assert stats["respawned"]               # a replacement was spawned
    assert svc._pending == 0 and svc.inflight == {}


# -- launch order: fullest block first ---------------------------------------
def _sized(rid, rng, n):
    return AlignRequest(rid=rid, kernel="global_affine",
                        query=rng.integers(0, 4, n).astype(np.uint8),
                        ref=rng.integers(0, 4, n).astype(np.uint8))


def test_full_bigger_bucket_launches_before_partial_smaller(rng):
    svc = AlignmentService(max_len=64, block=4)
    for i in range(2):
        svc.submit(_sized(i, rng, 12))           # bucket 16: half a block
    for i in range(2, 6):
        svc.submit(_sized(i, rng, 50))           # bucket 64: a full block
    _, bucket, jobs, coalesced, rows = svc._next_batch()
    assert bucket == (64, 64) and len(jobs) == rows == 4 and not coalesced
    _, bucket, jobs, coalesced, _ = svc._next_batch()
    assert bucket == (16, 16) and len(jobs) == 2 and not coalesced
    assert svc._next_batch() is None


def test_equal_occupancy_falls_back_to_smallest_area(rng):
    svc = AlignmentService(max_len=256, block=4, coalesce=False)
    for i, n in enumerate([200, 180, 50, 40, 14, 12]):
        svc.submit(_sized(i, rng, n))            # two rows at 256, 64, 16
    order = [svc._next_batch()[1] for _ in range(3)]
    assert order == [(16, 16), (64, 64), (256, 256)]


def test_coalescing_skips_donors_that_fill_their_own_block(rng):
    svc = AlignmentService(max_len=256, block=4)
    for i in range(4):
        svc.submit(_sized(i, rng, 50))           # bucket 64: fills a block
    for i in range(4, 6):
        svc.submit(_sized(i, rng, 200))          # bucket 256: cannot
    small = [_sized(9, rng, 12)]
    grown = svc._coalesce_batch("global_affine", (16, 16), small, 4)
    assert grown == (256, 256) and len(small) == 3
    assert len(svc.queues[("global_affine", (64, 64))]) == 4
    assert not svc.queues[("global_affine", (256, 256))]


@dataclasses.dataclass(eq=False)
class _Job:
    rid: int
    bucket: tuple
    result: object = None
    gen: int = 0
    waits: int = 0
    attempts: int = 0
    not_before: float = 0.0


class _BudgetRowsChannel(Channel):
    """Rows per bucket as a 2 GiB traceback budget sizes them; a launch
    needs no device."""
    name = "rows"
    ROWS = {(1024, 1024): 256, (2048, 2048): 256, (4096, 4096): 127,
            (8192, 8192): 31, (16384, 16384): 8}

    def bucket_of(self, job):
        return job.bucket

    def block_for(self, bucket):
        return self.ROWS[bucket]

    def launch(self, bucket, jobs, block):
        return jobs, bucket

    def land(self, job, row, host):
        job.result = {"row": row}
        return 1


def test_no_queue_is_passed_over_more_than_the_bound():
    """A seeded closed loop of 128 outstanding jobs over five buckets,
    whose small buckets never fill a block: each launch is checked
    against an independent count of the launches that passed each key
    over while it held jobs, and every bucket is still served in
    proportion to its arrivals."""
    gw = Gateway(pipeline_depth=1)
    ch = gw.register_channel(_BudgetRowsChannel())
    buckets = list(ch.ROWS)
    mix = np.array([0.018, 0.051, 0.205, 0.36, 0.366])
    rng = np.random.default_rng(15)
    arrived, served = collections.Counter(), collections.Counter()
    rid = 0

    def submit():
        nonlocal rid
        job = _Job(rid, buckets[rng.choice(len(buckets), p=mix / mix.sum())])
        arrived[job.bucket] += 1
        rid += 1
        gw._pending += 1
        gw._push(ch, job)

    passed = {}
    worst = 0
    for _ in range(128):
        submit()
    for _ in range(3000):
        waiting = {k for k, q in gw.queues.items() if q}
        item = gw._next_batch()
        got = ch.queue_key(item[1])
        passed = {k: passed.get(k, 0) + 1 for k in waiting if k != got}
        worst = max([worst, *passed.values()])
        assert worst <= Gateway.QUEUE_STALE_AFTER
        gw._harvest(item, gw._launch("w0", item))
        served.update(j.bucket for j in item[2])
        while gw._pending < 128:
            submit()
    reasons = gw.metrics()["metrics"]["counters"]
    assert reasons["gw_form_reason_total{reason=stale}"] > 0
    assert worst == Gateway.QUEUE_STALE_AFTER     # the bound is reached
    n_served, n_arrived = sum(served.values()), sum(arrived.values())
    for b in buckets:
        assert abs(served[b] / n_served - arrived[b] / n_arrived) < 0.03
    assert gw.drain() == 128 and gw._pending == 0


def test_form_span_and_counter_record_why_each_launch(rng):
    svc = AlignmentService(max_len=256, block=4)
    for i, n in enumerate([12, 14, 50, 40, 200, 210]):
        svc.submit(_sized(i, rng, n))            # two rows at 16, 64, 256
    obs_trace.clear()
    obs_trace.enable()
    try:
        assert svc.drain() == 6
    finally:
        obs_trace.disable()
    forms = [s.args for s in obs_trace.spans() if s.name == "gw.form"]
    obs_trace.clear()
    assert len(forms) == len(svc.dispatches) == 2
    # three half-full keys tie: 16 goes first and takes 64's two rows;
    # then 256 is the only key left
    assert [f["reason"] for f in forms] == ["tie", "fullest"]
    assert [f["occupancy"] for f in forms] == [1.0, 0.5]
    counters = svc.metrics()["metrics"]["counters"]
    by_reason = {k: v for k, v in counters.items()
                 if k.startswith("gw_form_reason_total")}
    assert sum(by_reason.values()) == len(svc.dispatches)
