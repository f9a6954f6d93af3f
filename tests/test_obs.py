"""Observability layer: span tracer, metrics registry, trace export,
and the gateway/plan-cache integration.

The contracts under test: the disabled path emits nothing, concurrent
writers never lose or corrupt each other's spans, rings wrap
oldest-first, tracing never changes a result, exported traces validate
against the Chrome trace-event schema with a named track per worker,
and the gateway's metrics snapshot reconciles exactly with its futures
— clean runs and faulty ones alike.
"""
from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace
from repro.serve import (AlignRequest, AlignmentService, FaultPlan,
                         InjectedFault)


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts and ends with tracing off, empty, and at the
    default ring capacity (``enable(capacity=...)`` is sticky)."""
    trace.disable()
    trace.clear()
    trace._CAPACITY = trace._DEFAULT_CAPACITY
    yield
    trace.disable()
    trace.clear()
    trace._CAPACITY = trace._DEFAULT_CAPACITY


def _req(rid, rng, n=12, kernel="global_affine"):
    return AlignRequest(rid=rid, kernel=kernel,
                        query=rng.integers(0, 4, n).astype(np.uint8),
                        ref=rng.integers(0, 4, n + 2).astype(np.uint8))


# -- trace: disabled path ----------------------------------------------------
def test_disabled_path_emits_nothing():
    assert not trace.enabled()
    with trace.span("x", cat="t", a=1) as sp:
        sp.set(b=2)
    trace.instant("y", cat="t")
    trace.counter("z", 3.0)

    @trace.traced
    def f(v):
        return v + 1

    assert f(1) == 2
    assert trace.spans() == []
    assert trace.counters() == []
    assert trace.dropped() == 0
    # the disabled span() is one branch returning a shared singleton
    assert trace.span("a") is trace.span("b") is trace._NOOP


@pytest.fixture
def bridge():
    trace.enable_jax_bridge()
    yield
    trace.disable_jax_bridge()


def test_bridge_with_tracing_off_is_still_the_shared_noop(bridge):
    assert not trace.enabled()
    assert trace.span("a") is trace.span("b") is trace._NOOP
    with trace.span("x", cat="t", a=1):
        pass
    assert trace.spans() == []


def test_bridged_spans_carry_distinct_ids(bridge):
    """With tracing and the bridge on, every span (decorated ones too)
    opens a profiler annotation and records the id it gave it."""
    trace.enable()

    @trace.traced(name="f")
    def f():
        with trace.span("inner", cat="t", a=1):
            pass

    f()
    with trace.span("x", cat="t") as sp:
        sp.set(b=2)
    by = {s.name: s.args for s in trace.spans()}
    assert by["inner"]["a"] == 1 and by["x"]["b"] == 2
    ids = [a["span_id"] for a in by.values()]
    assert len(set(ids)) == 3


def test_enable_disable_round_trip():
    trace.enable()
    with trace.span("on", cat="t"):
        pass
    trace.disable()
    with trace.span("off", cat="t"):
        pass
    names = [s.name for s in trace.spans()]
    assert names == ["on"]


# -- trace: recording semantics ----------------------------------------------
def test_span_records_interval_and_args():
    trace.enable()
    with trace.span("gw.launch", cat="gateway", worker="w0") as sp:
        sp.set(n=8)
    (s,) = trace.spans()
    assert s.name == "gw.launch" and s.cat == "gateway"
    assert s.t1 is not None and s.t1 >= s.t0
    assert s.tid == threading.current_thread().name
    assert s.args == {"worker": "w0", "n": 8}


def test_span_drop_suppresses():
    trace.enable()
    with trace.span("gw.form", cat="gateway") as sp:
        sp.drop()
    assert trace.spans() == []
    assert trace.dropped() == 0      # drop() is not a ring eviction


def test_instant_has_no_duration():
    trace.enable()
    trace.instant("gw.retry", cat="gateway", n=2)
    (s,) = trace.spans()
    assert s.t1 is None and s.args == {"n": 2}


def test_traced_decorator_bare_and_configured():
    trace.enable()

    @trace.traced
    def plain():
        return 1

    @trace.traced(name="map.extend", cat="mapper")
    def named():
        return 2

    assert plain() == 1 and named() == 2
    names = {(s.name, s.cat) for s in trace.spans()}
    assert ("map.extend", "mapper") in names
    assert any(n.endswith("plain") and c == "fn" for n, c in names)


def test_span_survives_exception():
    trace.enable()
    with pytest.raises(ValueError):
        with trace.span("gw.launch", cat="gateway"):
            raise ValueError("boom")
    assert [s.name for s in trace.spans()] == ["gw.launch"]


# -- trace: bounded memory ---------------------------------------------------
def test_ring_wraparound_drops_oldest_first():
    trace.enable(capacity=8)
    for i in range(20):
        trace.instant(f"ev{i}", cat="t")
    kept = [s.name for s in trace.spans()]
    assert kept == [f"ev{i}" for i in range(12, 20)]   # newest 8 survive
    assert trace.dropped() == 12


def test_clear_resets_everything():
    trace.enable(capacity=4)
    for i in range(9):
        trace.instant(f"ev{i}", cat="t")
    trace.counter("c", 1.0)
    trace.clear()
    assert trace.spans() == [] and trace.counters() == []
    assert trace.dropped() == 0
    trace.instant("fresh", cat="t")              # new epoch ring works
    assert [s.name for s in trace.spans()] == ["fresh"]


# -- trace: concurrency ------------------------------------------------------
def test_concurrent_workers_interleave_without_loss():
    trace.enable(capacity=4096)
    n_threads, n_spans = 4, 500
    start = threading.Barrier(n_threads)

    def work(widx):
        start.wait()
        for i in range(n_spans):
            with trace.span("w.step", cat="t", w=widx, i=i):
                pass
        trace.counter(f"done{widx}", widx)

    threads = [threading.Thread(target=work, args=(w,), name=f"tw{w}")
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    spans = trace.spans()
    assert len(spans) == n_threads * n_spans
    assert trace.dropped() == 0
    by_tid = {}
    for s in spans:
        by_tid.setdefault(s.tid, []).append(s)
    assert set(by_tid) == {f"tw{w}" for w in range(n_threads)}
    for tid, ss in by_tid.items():
        widx = int(tid[2:])
        # no cross-thread corruption: every span carries its writer's id
        assert all(s.args["w"] == widx for s in ss)
        # per-thread order preserved (single-writer ring)
        assert [s.args["i"] for s in ss] == list(range(n_spans))
    assert len(trace.counters()) == n_threads


# -- metrics -----------------------------------------------------------------
def test_counter_monotonic():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("req_total", channel="a")
    c.inc()
    c.inc(3)
    assert c.value == 4.0
    assert reg.counter("req_total", channel="a") is c   # same series
    assert reg.counter("req_total", channel="b") is not c
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_up_down():
    g = obs_metrics.MetricsRegistry().gauge("depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6.0


def test_histogram_percentiles_within_bucket_error():
    h = obs_metrics.MetricsRegistry().histogram("lat")
    h.observe(0.250)
    # one observation: clamping makes the estimate exact
    assert h.quantile(0.5) == pytest.approx(0.250)
    for v in [0.001 * i for i in range(1, 1000)]:
        h.observe(v)
    p = h.percentiles()
    root2 = 2.0 ** 0.5
    assert 0.5 / root2 <= p["p50"] <= 0.5 * root2
    assert 0.95 / root2 <= p["p95"] <= 0.999
    assert h.count == 1000 and h.min == 0.001 and h.max == 0.999
    assert h.quantile(0.99) <= h.max


def test_histogram_underflow_bucket():
    h = obs_metrics.MetricsRegistry().histogram("neg")
    h.observe(0.0)
    h.observe(-1.0)
    assert h.count == 2
    assert h.quantile(0.5) == -1.0          # underflow reports the min


def test_snapshot_and_prometheus_formats():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("gw_dead_letters_total", kind="retries").inc(2)
    reg.gauge("gw_queue_depth", channel="align").set(7)
    reg.histogram("gw_latency_s").observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["gw_dead_letters_total{kind=retries}"] == 2.0
    assert snap["gauges"]["gw_queue_depth{channel=align}"] == 7.0
    hist = snap["histograms"]["gw_latency_s"]
    assert hist["count"] == 1 and hist["p50"] == pytest.approx(0.5)
    json.dumps(snap)                          # JSON-safe end to end

    text = reg.prometheus()
    assert "# TYPE gw_dead_letters_total counter" in text
    assert 'gw_dead_letters_total{kind="retries"} 2' in text
    assert "# TYPE gw_latency_s summary" in text
    assert 'gw_latency_s{quantile="0.5"}' in text
    assert "gw_latency_s_count 1" in text


# -- compile ledger ----------------------------------------------------------
def test_compile_ledger_caps_oldest_first():
    led = obs_metrics.CompileLedger(cap=2)
    led.record("a", 1.0)
    led.record("b", 2.0)
    led.record("a", 0.5)                      # refresh: a is now newest
    led.record("c", 3.0)                      # evicts b (oldest)
    snap = led.snapshot()
    assert set(snap) == {"a", "c"}
    assert snap["a"] == {"compile_s": 1.5, "compiles": 2,
                         "calls": 0, "hits": 0}
    led.update_usage("a", calls=10, hits=9)
    led.update_usage("b", calls=5, hits=5)    # evicted: silently dropped
    assert led.snapshot()["a"]["calls"] == 10
    led.clear()
    assert len(led) == 0


def test_compile_ledger_survives_plan_cache_clear(rng):
    from repro.runtime import plan as plan_mod
    svc = AlignmentService(max_len=16, block=2)
    svc.submit(_req(0, rng, n=8))
    svc.drain()
    info = plan_mod.plan_cache_info()
    ledger = info["compile_ledger"]
    keys = [k for k in ledger if "global_affine" in k]
    assert keys, f"no global_affine entry in ledger: {list(ledger)}"
    key = keys[0]
    assert ledger[key]["compiles"] >= 1
    assert ledger[key]["compile_s"] > 0.0

    plan_mod.clear_plan_cache(keep_stats=True)
    after = plan_mod.plan_cache_info()["compile_ledger"]
    # per-key compile_s survives the clear, and the retired plan's
    # dispatch counters are folded into its entry
    assert after[key]["compile_s"] == ledger[key]["compile_s"]
    assert after[key]["calls"] >= 1


# -- export ------------------------------------------------------------------
def test_chrome_trace_export_schema_and_tracks():
    trace.enable()
    with trace.span("gw.launch", cat="gateway", worker="w0", n=4):
        trace.instant("gw.retry", cat="gateway")
    trace.counter("gw.queue_depth", 3)
    obj = obs_export.to_chrome_trace()
    assert obs_export.validate_chrome_trace(obj) == []
    evs = obj["traceEvents"]
    assert {e["ph"] for e in evs} == {"M", "X", "i", "C"}
    # timestamps are relative: the earliest timed event sits at 0
    assert min(e["ts"] for e in evs if e["ph"] != "M") == 0.0
    (x,) = [e for e in evs if e["ph"] == "X"]
    assert x["dur"] >= 0 and x["args"]["worker"] == "w0"
    (c,) = [e for e in evs if e["ph"] == "C"]
    assert c["tid"] == 0 and c["args"] == {"value": 3.0}
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert threading.current_thread().name in names
    json.dumps(obj)


def test_validate_chrome_trace_catches_malformed():
    assert obs_export.validate_chrome_trace([]) \
        == ["top level must be a dict with a 'traceEvents' list"]
    errs = obs_export.validate_chrome_trace({"traceEvents": [
        {"ph": "X", "pid": 1},                              # missing name
        {"name": "a", "ph": "Z", "pid": 1, "ts": 0},        # bad phase
        {"name": "b", "ph": "X", "pid": 1, "ts": 0, "dur": -1},
        {"name": "c", "ph": "X", "pid": 1, "ts": -5, "dur": 1},
        {"name": "d", "ph": "C", "pid": 1, "ts": 0, "args": {}},
    ]})
    assert len(errs) == 5


# -- gateway integration -----------------------------------------------------
def test_gateway_metrics_reconcile_clean_run(rng):
    trace.enable()
    svc = AlignmentService(max_len=16, block=2)
    n = 6
    for i in range(n):
        svc.submit(_req(i, rng, n=8))
    svc.drain()
    m = svc.metrics()
    rec = m["reconcile"]
    assert rec == {"submitted": n, "resolved": n, "dead_lettered": 0,
                   "ok": True}
    counters = m["metrics"]["counters"]
    assert counters["gw_submitted_total"] == n
    assert counters["gw_completed_total"] == n
    lat = m["metrics"]["histograms"]["gw_latency_s{outcome=completed}"]
    assert lat["count"] == n and lat["p50"] > 0.0
    assert m["plan_cache"]["calls"] >= 1
    json.dumps(m)
    # the drain recorded launch + harvest spans on this thread
    names = {s.name for s in trace.spans()}
    assert {"gw.launch", "gw.harvest"} <= names


def test_gateway_metrics_reconcile_with_dead_letters(rng):
    svc = AlignmentService(max_len=16, block=2, max_retries=0,
                           fault_plan=FaultPlan(seed=1, fail_launch_p=1.0))
    fut = svc.submit(_req(0, rng, n=8))
    with pytest.raises(InjectedFault):
        svc.drain()
    assert fut.result()["failed"]
    m = svc.metrics()
    rec = m["reconcile"]
    assert rec["ok"] and rec["submitted"] == 1 and rec["dead_lettered"] == 1
    assert m["metrics"]["counters"][
        "gw_dead_letters_total{kind=retries}"] == 1
    assert m["dead_letters_by_kind"] == {"retries": 1}


def test_dead_letter_records_carry_worker_attempts_ts(rng):
    svc = AlignmentService(max_len=16, block=2, max_retries=1,
                           fault_plan=FaultPlan(seed=1, fail_launch_p=1.0))
    svc.submit(_req(0, rng, n=8))
    for _ in range(2):
        with pytest.raises(InjectedFault):
            svc.drain()
    (d,) = svc.dead_letters
    assert d["kind"] == "retries" and d["rid"] == 0
    assert d["attempts"] == 2                  # initial try + one retry
    assert isinstance(d["worker"], str)
    assert isinstance(d["ts"], float)


def test_shed_dead_letter_attributed_to_submit(rng):
    svc = AlignmentService(max_len=16, block=2, max_pending=1,
                           backpressure="shed")
    svc.submit(_req(0, rng, n=8))
    f1 = svc.submit(_req(1, rng, n=8))        # past budget: shed
    assert f1.result()["error"]["kind"] == "shed"
    (d,) = svc.dead_letters
    assert d["kind"] == "shed" and d["worker"] == "submit"
    m = svc.metrics()
    # shed requests still count as submitted, and still reconcile
    assert m["reconcile"]["submitted"] == 2
    svc.drain()
    assert svc.metrics()["reconcile"]["ok"]


def _genotype_site(rid, seed):
    from repro.data.synthetic import sample_site
    from repro.serve import GenotypeRequest
    site = sample_site(seed=seed, n_reads=6, genotype=(0, 1))
    return GenotypeRequest(rid=rid, reads=site.reads,
                           haplotypes=site.haplotypes)


@pytest.mark.parametrize("service", ["alignment", "genotyping"])
def test_harvest_spans_count_the_cells_of_what_they_landed(service, rng):
    """Each harvested batch records the cells its jobs asked for and the
    cells its plan computed; the counters hold the same sums."""
    trace.enable()
    if service == "alignment":
        svc = AlignmentService(max_len=32, block=4)
        reqs = [AlignRequest(
            rid=i, kernel="global_affine",
            query=rng.integers(0, 4, int(n)).astype(np.uint8),
            ref=rng.integers(0, 4, int(n) + 3).astype(np.uint8))
            for i, n in enumerate(rng.integers(4, 28, 11))]
        futs = [svc.submit(r) for r in reqs]
        pairs = [(r.query, r.ref) for r in reqs]
    else:
        from repro.serve import GenotypingService
        svc = GenotypingService(max_len=64, block=4)
        sites = [_genotype_site(k, 40 + k) for k in range(2)]
        futs = [svc.submit(s) for s in sites]
        pairs = [(q, h) for s in sites for q in s.reads
                 for h in s.haplotypes]
    svc.drain()
    assert all(f.done() and "failed" not in f.result() for f in futs)
    harvests = [s.args for s in trace.spans() if s.name == "gw.harvest"]
    useful = sum(a["cells_useful"] for a in harvests)
    launched = sum(a["cells_launched"] for a in harvests)
    assert useful == sum(len(q) * len(r) for q, r in pairs)
    assert all(0 < a["cells_useful"] <= a["cells_launched"]
               for a in harvests)
    counters = svc.metrics()["metrics"]["counters"]
    assert counters["gw_cells_useful_total"] == useful
    assert counters["gw_cells_launched_total"] == launched


def _short_reads(rng, n=11):
    return [AlignRequest(
        rid=i, kernel="global_affine",
        query=rng.integers(0, 4, int(k)).astype(np.uint8),
        ref=rng.integers(0, 4, int(k) + 3).astype(np.uint8))
        for i, k in enumerate(rng.integers(4, 28, n))]


def test_host_work_spans_nest_and_match_the_counter(rng):
    """``gw.materialize`` and ``gw.land`` lie inside ``gw.harvest``,
    ``gw.cigar`` inside ``gw.land``, and ``gw.pad`` inside ``gw.launch``;
    ``n`` counts the rows landed, encoded once per batch, and padded; the
    spans that were there keep their args; and
    ``gw_host_seconds_total`` holds the pad and land time of each batch,
    taken inside those spans."""
    trace.enable()
    svc = AlignmentService(max_len=32, block=4)
    reqs = _short_reads(rng)
    futs = [svc.submit(r) for r in reqs]
    svc.drain()
    assert all(f.done() and "cigar" in f.result() for f in futs)
    spans = trace.spans()

    def named(name):
        return [s for s in spans if s.name == name]

    def inside(child, parents):
        return sum(p.tid == child.tid and p.t0 <= child.t0
                   and child.t1 <= p.t1 for p in parents) == 1

    harvests, launches = named("gw.harvest"), named("gw.launch")
    mats, lands, pads = named("gw.materialize"), named("gw.land"), \
        named("gw.pad")
    assert len(mats) == len(lands) == len(harvests) > 1
    assert len(pads) == len(launches)
    assert all(inside(s, harvests) for s in mats + lands)
    assert all(inside(s, launches) for s in pads)
    cigars = named("gw.cigar")
    assert len(cigars) == len(lands)
    assert all(inside(s, lands) for s in cigars)
    assert all(set(s.args) == {"channel", "n"} for s in cigars)
    for c in cigars:                    # every row launched, pads left out
        land, = (s for s in lands if s.t0 <= c.t0 and c.t1 <= s.t1)
        h, = (s for s in harvests if s.t0 <= c.t0 and c.t1 <= s.t1)
        assert c.args["n"] == h.args["n"] >= land.args["n"]
    for m, land in zip(mats, lands):
        assert m.t1 <= land.t0          # the wait comes before the rows
    assert sum(s.args["n"] for s in lands) == len(reqs)
    assert [s.args["n"] for s in pads] == [s.args["n"] for s in launches]
    assert all(set(s.args) == {"worker", "channel", "n"} for s in lands)
    assert all(set(s.args) == {"worker", "channel", "seq", "n", "done",
                               "cells_useful", "cells_launched"}
               for s in harvests)
    assert all(set(s.args) == {"worker", "channel", "seq", "n"}
               for s in launches)
    counters = svc.metrics()["metrics"]["counters"]
    for phase, ss in (("pad", pads), ("land", lands)):
        in_spans = sum(s.t1 - s.t0 for s in ss)
        counted = counters[f"gw_host_seconds_total{{phase={phase}}}"]
        assert 0 < counted <= in_spans
        assert in_spans - counted < 1e-3 * len(ss)


def test_host_seconds_are_counted_with_tracing_off(rng):
    """The counter does not depend on tracing; no span is recorded."""
    svc = AlignmentService(max_len=32, block=4)
    futs = [svc.submit(r) for r in _short_reads(rng, 6)]
    svc.drain()
    assert all(f.done() for f in futs)
    assert trace.spans() == []
    counters = svc.metrics()["metrics"]["counters"]
    assert counters["gw_host_seconds_total{phase=pad}"] > 0
    assert counters["gw_host_seconds_total{phase=land}"] > 0


def test_traced_serve_keeps_results_and_names_each_worker_track(rng):
    """Tracing only observes: a two-worker ``serve()`` gives the same
    results with it on as with it off, and its export validates with a
    named track for every worker that launched a batch."""
    base = _short_reads(rng, 24)
    results = {}
    for traced in (False, True):
        if traced:
            trace.enable()
        reqs = [AlignRequest(rid=r.rid, kernel=r.kernel, query=r.query,
                             ref=r.ref) for r in base]
        svc = AlignmentService(max_len=32, block=4, pipeline_depth=2)
        svc.submit_all(reqs)
        svc.serve(n_workers=2, timeout_s=120.0)
        results[traced] = [r.result for r in reqs]
    obj = obs_export.to_chrome_trace()
    assert obs_export.validate_chrome_trace(obj) == []
    assert results[True] == results[False]
    workers = {s.args["worker"] for s in trace.spans()
               if s.name == "gw.launch"}
    tracks = {e["args"]["name"] for e in obj["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert workers and {f"gw-{w}" for w in workers} <= tracks


def test_dump_trace_writes_valid_file(tmp_path, rng):
    trace.enable()
    svc = AlignmentService(max_len=16, block=2)
    svc.submit(_req(0, rng, n=8))
    svc.drain()
    path = tmp_path / "trace.json"
    obj = svc.dump_trace(str(path))
    assert obs_export.validate_chrome_trace(obj) == []
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(obj))
    assert any(e["ph"] == "X" for e in on_disk["traceEvents"])


def test_prometheus_surface_on_gateway(rng):
    svc = AlignmentService(max_len=16, block=2)
    svc.submit(_req(0, rng, n=8))
    svc.drain()
    text = svc.prometheus()
    assert "gw_submitted_total 1" in text
    assert "# TYPE gw_latency_s summary" in text
