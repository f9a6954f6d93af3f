"""Fault-tolerance bookkeeping + serving behaviour."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro import configs
from repro.ft import ALIVE, DEAD, STRAGGLER, HeartbeatMonitor, plan_mesh
from repro.serve import AlignRequest, AlignmentService, Request, ServeSession


def test_heartbeat_states():
    m = HeartbeatMonitor(dead_after=10.0, straggler_factor=3.0)
    for t in range(5):
        m.beat("w0", now=float(t))
        m.beat("w1", now=float(t))
    m.beat("w0", now=5.0)
    assert m.status("w0", now=5.5) == ALIVE
    assert m.status("w1", now=8.9) == STRAGGLER      # 4.9s vs 1s median
    assert m.status("w1", now=15.1) == DEAD
    assert m.status("unknown", now=0.0) == DEAD
    assert m.dead_workers(now=14.9) == ["w1"]        # w0 is only a straggler
    # at 7.5: w0 gap 2.5 (alive), w1 gap 3.5 (> 3x median -> straggler)
    assert m.alive_workers(now=7.5) == ["w0"]


def test_heartbeat_forget_drops_worker_and_median_skew():
    m = HeartbeatMonitor(dead_after=200.0, straggler_factor=3.0)
    for t in range(5):
        m.beat("w0", now=float(t))           # 1s intervals
        m.beat("slow", now=float(t) * 100.0)  # 100s intervals
    # the slow worker's history dominates the fleet median (100s), so a
    # 4s gap on w0 reads as ALIVE
    assert m.status("w0", now=8.0) == ALIVE
    assert m.forget("slow") is True
    # with its intervals gone the median is w0's 1s: 4s gap > 3x median
    assert m.status("w0", now=8.0) == STRAGGLER
    assert "slow" not in m.fleet(now=8.0)
    assert m.status("slow", now=8.0) == DEAD   # untracked reads as dead
    assert m.forget("slow") is False           # already gone
    assert m.forget("never-seen") is False


def test_heartbeat_median_cache_tracks_beats():
    m = HeartbeatMonitor()
    m.beat("w0", now=0.0)
    m.beat("w0", now=10.0)
    assert m._median_interval() == 10.0
    m.beat("w0", now=30.0)                     # new interval: cache refresh
    assert m._median_interval() == 20.0        # median of [10, 20] -> upper


def test_plan_mesh_elastic():
    assert plan_mesh(512, 16, pod_size=256) == (2, 16, 16)
    assert plan_mesh(256, 16) == (16, 16)
    # lose a node: largest usable shrinks, TP preserved
    assert plan_mesh(255, 16) == (15, 16)
    # TP preserved as long as one replica fits (memory constraint)
    assert plan_mesh(24, 16) == (1, 16)
    # below one replica: TP degrades by powers of two
    assert plan_mesh(12, 16) == (1, 8)
    assert plan_mesh(1, 16) == (1, 1)


def test_plan_mesh_edge_cases():
    # an empty (or negative) fleet has no mesh: hard error, not (0, ...)
    with pytest.raises(ValueError, match="n_devices"):
        plan_mesh(0, 4)
    with pytest.raises(ValueError, match="n_devices"):
        plan_mesh(-3, 4)
    with pytest.raises(ValueError, match="model_degree"):
        plan_mesh(4, 0)
    # non-power-of-two TP degree: preserved while a replica fits ...
    assert plan_mesh(6, 6) == (1, 6)
    assert plan_mesh(12, 6) == (2, 6)
    assert plan_mesh(5, 6) == (1, 3)      # ... else halves (6 -> 3)
    assert plan_mesh(3, 6) == (1, 3)
    assert plan_mesh(2, 6) == (2, 1)      # 3 -> 1: pure data parallel
    assert plan_mesh(1, 1) == (1, 1)


def test_alignment_service_end_to_end(rng):
    from repro.core import align, kernels_zoo
    svc = AlignmentService(max_len=64, block=4)
    qs = [rng.integers(0, 4, rng.integers(10, 40)).astype(np.uint8)
          for _ in range(10)]
    rs = [rng.integers(0, 4, rng.integers(10, 40)).astype(np.uint8)
          for _ in range(10)]
    for i in range(10):
        svc.submit(AlignRequest(rid=i, kernel="global_affine",
                                query=qs[i], ref=rs[i]))
    # heterogeneous second channel (paper: mixed kernels via N_K)
    svc.submit(AlignRequest(rid=10, kernel="local_linear",
                            query=qs[0], ref=rs[0]))
    reqs = [r for q in svc.queues.values() for r in q]
    assert svc.drain() == 11
    import jax.numpy as jnp
    spec, params = kernels_zoo.make("global_affine")
    for r in reqs[:3]:
        if r.kernel != "global_affine":
            continue
        direct = align(spec, params, jnp.asarray(r.query),
                       jnp.asarray(r.ref), with_traceback=False)
        assert r.result["score"] == pytest.approx(float(direct.score))


def _fake_inflight(svc, worker, req):
    """Install a hand-built in-flight batch (as if ``_launch`` ran but the
    worker wedged before harvest)."""
    from repro.serve import InflightBatch
    ib = InflightBatch(worker=worker, kernel=req.kernel, bucket=(16, 16),
                       reqs=[req], gens=[req.gen], out=None)
    svc.inflight.setdefault(worker, []).append(ib)
    return ib


def test_alignment_service_redispatch():
    svc = AlignmentService(max_len=32, block=2, redispatch_after=5.0)
    svc.monitor.beat("w1", now=0.0)
    req = AlignRequest(0, "global_affine", np.zeros(4, np.uint8),
                       np.zeros(4, np.uint8))
    _fake_inflight(svc, "w1", req)
    assert svc.redispatch_dead(now=1.0) == 0        # still alive
    assert svc.redispatch_dead(now=20.0) == 1       # dead -> requeued
    requeued = [r for (k, _), q in svc.queues.items()
                if k == "global_affine" for r in q]
    assert len(requeued) == 1
    assert requeued[0].gen == 1                     # generation bumped


def test_redispatch_discards_late_original_result(rng):
    """A re-dispatched request and its original in-flight batch must not
    both complete: the late original harvest is a stale generation and is
    discarded (regression: double-completion race)."""
    svc = AlignmentService(max_len=32, block=2, redispatch_after=5.0)
    req = AlignRequest(0, "global_affine",
                       rng.integers(0, 4, 12).astype(np.uint8),
                       rng.integers(0, 4, 12).astype(np.uint8))
    # launch on w1 for real (device output pending), then w1 goes dead
    item = ("global_affine", (16, 16), [req], False, svc.block)
    stale = svc._launch("w1", item)
    svc.monitor._last["w1"] = 0.0                   # silence its heartbeat
    assert svc.redispatch_dead(now=100.0) == 1      # requeued, gen bumped
    assert req.gen == 1 and req.result is None
    # the re-dispatched copy completes on a healthy worker
    assert svc.drain(worker="w2") == 1
    first = req.result
    assert first is not None
    # ... and the late original batch finally lands: must be discarded
    assert svc._harvest(item, stale) == 0
    assert req.result is first


def test_block_landing_matches_per_row_and_skips_stale(rng, monkeypatch):
    """A harvested block lands, for every request, the score, end cell and
    CIGAR that a row-by-row read of the device arrays gives; a request
    re-dispatched since the launch keeps no result from it, and the pad
    rows land nowhere."""
    from repro.core.traceback import moves_to_cigar
    from repro.serve import alignment_service as svc_mod
    svc = AlignmentService(max_len=32, block=8)
    reqs = [AlignRequest(i, "global_affine",
                         rng.integers(0, 4, int(k)).astype(np.uint8),
                         rng.integers(0, 4, int(k) + 3).astype(np.uint8))
            for i, k in enumerate(rng.integers(4, 13, 5))]
    seen = {}
    real_materialize = svc_mod._AlignChannel.materialize

    def keep(self, out):
        seen["host"] = real_materialize(self, out)
        return seen["host"]

    monkeypatch.setattr(svc_mod._AlignChannel, "materialize", keep)
    item = ("global_affine", (16, 16), reqs, False, 8)
    ib = svc._launch("w0", item)
    reqs[2].gen += 1                        # re-dispatched since the launch
    assert svc._harvest(item, ib) == 4
    score, end_i, end_j, moves, n_moves = seen["host"]
    assert moves.shape[0] == 8              # three pad rows
    assert reqs[2].result is None
    for i, r in enumerate(reqs):
        if i == 2:
            continue
        assert r.result == {"score": float(score[i]),
                            "end": (int(end_i[i]), int(end_j[i])),
                            "cigar": moves_to_cigar(moves[i],
                                                    int(n_moves[i]))}
        assert type(r.result["score"]) is float
        assert all(type(e) is int for e in r.result["end"])


def test_drain_requeues_requests_on_dispatch_failure(rng, monkeypatch):
    """If dispatch raises, the popped requests must go back to the queues
    and nothing may linger in ``inflight`` (regression: lost requests)."""
    from repro.runtime import plan as plan_mod
    svc = AlignmentService(max_len=64, block=4)
    reqs = [AlignRequest(rid=i, kernel="global_affine",
                         query=rng.integers(0, 4, 20).astype(np.uint8),
                         ref=rng.integers(0, 4, 20).astype(np.uint8))
            for i in range(6)]
    for r in reqs:
        svc.submit(r)
    real_get_plan = plan_mod.get_plan
    calls = {"n": 0}

    def exploding_get_plan(*a, **kw):
        calls["n"] += 1
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(plan_mod, "get_plan", exploding_get_plan)
    with pytest.raises(RuntimeError, match="injected"):
        svc.drain()
    assert calls["n"] == 1
    queued = [r for q in svc.queues.values() for r in q]
    assert len(queued) == 6                         # nothing lost
    assert svc.inflight == {}                       # nothing leaked
    # after the fault clears, the same queue drains to completion
    monkeypatch.setattr(plan_mod, "get_plan", real_get_plan)
    assert svc.drain() == 6
    assert all(r.result is not None for r in reqs)


def test_wait_requeues_window_on_harvest_failure(rng, monkeypatch):
    """A failure while harvesting batch N must also recover the launched-
    but-unharvested batches behind it in the pipeline window."""
    svc = AlignmentService(max_len=64, block=2, pipeline_depth=3)
    reqs = [AlignRequest(rid=i, kernel="global_affine",
                         query=rng.integers(0, 4, 20).astype(np.uint8),
                         ref=rng.integers(0, 4, 20).astype(np.uint8))
            for i in range(6)]
    for r in reqs:
        svc.submit(r)
    from repro.serve import alignment_service as svc_mod
    boom = {"armed": True}
    real_cigars = svc_mod.block_cigars

    def exploding_cigars(*args, **kwargs):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected harvest failure")
        return real_cigars(*args, **kwargs)

    monkeypatch.setattr(svc_mod, "block_cigars", exploding_cigars)
    with pytest.raises(RuntimeError, match="injected"):
        svc.drain()
    queued = [r for q in svc.queues.values() for r in q]
    assert len(queued) == 6                         # full recovery
    assert svc.inflight == {}
    assert svc.drain() == 6
    assert all(r.result is not None for r in reqs)


def test_submit_returns_future(rng):
    svc = AlignmentService(max_len=64, block=4)
    fut = svc.submit(AlignRequest(rid=0, kernel="global_affine",
                                  query=rng.integers(0, 4, 20).astype(np.uint8),
                                  ref=rng.integers(0, 4, 24).astype(np.uint8)))
    assert not fut.done()
    res = fut.result()                              # pumps the dispatcher
    assert fut.done() and res is fut.req.result
    assert "score" in res and "cigar" in res
    with pytest.raises(ValueError, match="exceed max_len"):
        svc.submit(AlignRequest(rid=1, kernel="global_affine",
                                query=np.zeros(80, np.uint8),
                                ref=np.zeros(10, np.uint8)))


def _mixed_stream(rng, n=24):
    """Mixed buckets incl. partial batches so coalescing kicks in."""
    sizes = [12, 14, 40, 50, 20, 60, 30, 35]
    reqs = []
    for i in range(n):
        s = sizes[i % len(sizes)]
        reqs.append(AlignRequest(
            rid=i, kernel="global_affine",
            query=rng.integers(0, 4, s).astype(np.uint8),
            ref=rng.integers(0, 4, s + 3).astype(np.uint8)))
    return reqs


def _clone(reqs):
    return [AlignRequest(rid=r.rid, kernel=r.kernel, query=r.query,
                         ref=r.ref) for r in reqs]


def test_sync_vs_pipelined_drain_equivalence(rng):
    """Pipelined drain returns bit-identical results in the same request
    order and the same dispatch sequence as the synchronous path,
    including coalesced batches."""
    base = _mixed_stream(rng)
    results, dispatches = {}, {}
    for depth in (1, 2, 4):
        svc = AlignmentService(max_len=64, block=4, pipeline_depth=depth)
        reqs = _clone(base)
        for r in reqs:
            svc.submit(r)
        assert svc.drain() == len(reqs)
        results[depth] = [r.result for r in reqs]
        dispatches[depth] = list(svc.dispatches)
    assert any(d["coalesced"] for d in dispatches[1])
    for depth in (2, 4):
        assert results[depth] == results[1]          # bit-identical
        assert dispatches[depth] == dispatches[1]    # same batch sequence


def test_pipelined_drain_after_redispatch_matches_sync(rng):
    """Equivalence holds across a redispatch: results land once, match
    the synchronous path, and every request completes."""
    base = _mixed_stream(rng, n=8)
    sync = AlignmentService(max_len=64, block=4, pipeline_depth=1)
    sync_reqs = _clone(base)
    for r in sync_reqs:
        sync.submit(r)
    sync.drain()

    svc = AlignmentService(max_len=64, block=4, pipeline_depth=2,
                           redispatch_after=5.0)
    reqs = _clone(base)
    futs = [svc.submit(r) for r in reqs]
    # one batch launches on a worker that then goes dead
    item = svc._next_batch()
    svc._launch("w_dead", item)
    svc.monitor._last["w_dead"] = 0.0               # silence its heartbeat
    assert svc.redispatch_dead(now=100.0) == len(item[2])
    assert svc.drain(worker="w_ok") == len(reqs)
    assert all(f.done() for f in futs)
    assert [r.result for r in reqs] == [r.result for r in sync_reqs]


@pytest.mark.slow   # loads a reduced LM
def test_serve_session_matches_direct_rollout(rng):
    """Slot-based decode == direct greedy rollout via forward()."""
    import jax.numpy as jnp
    from repro.models import get_model
    cfg = configs.get("olmo-1b", reduced=True)
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    max_new = 6
    # direct rollout
    toks = list(prompt)
    for _ in range(max_new):
        out = model.forward(cfg, params,
                            {"tokens": jnp.asarray(toks)[None]})
        toks.append(int(jnp.argmax(out["logits"][0, -1])))
    want = toks[len(prompt):]
    sess = ServeSession(cfg, params, batch_slots=2, max_len=64)
    req = Request(rid=0, prompt=prompt, max_new=max_new)
    done = sess.run([req])
    assert done and done[0].out == want


@pytest.mark.slow   # loads a reduced LM
def test_serve_session_multi_slot(rng):
    from repro.models import get_model
    cfg = configs.get("olmo-1b", reduced=True)
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        4 + i).astype(np.int32),
                    max_new=4)
            for i in range(5)]           # 5 requests > 2 slots: queuing
    sess = ServeSession(cfg, params, batch_slots=2, max_len=48)
    done = sess.run(reqs)
    assert len(done) == 5
    assert all(len(r.out) == 4 for r in done)
