"""The plan program's phases and the spans on the profiler's clock
(bench/phases.py), and the harvested-cell reader: on synthetic events,
and on a traced run of the harness on the CPU."""
from __future__ import annotations

import io
import json
import shutil
import sys
import time
from pathlib import Path
from typing import NamedTuple

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import devtrace, harness, phases  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402

FILL, TB = phases.PHASES
BIG_SEED = 2**31 + 4099


class Event(NamedTuple):
    name: str
    start_ns: int
    duration_ns: int
    stats: tuple = ()


# a fill loop with a scoped and an unscoped op nested in it, an unscoped
# op between the programs, and a traceback loop with a nested op
OPS = [(FILL, 0.0, 10.0), (None, 1.0, 2.0), (FILL, 3.0, 4.0),
       (FILL, 3.5, 4.0), (None, 11.0, 12.0), (TB, 12.0, 20.0),
       (None, 13.0, 14.0), (TB, 13.5, 16.0)]


@pytest.mark.parametrize("lo,hi,want", [
    (0.0, 20.0, {FILL: 10.0, TB: 8.0, phases.UNSCOPED: 1.0}),
    (3.2, 13.6, {FILL: 6.8, TB: 1.6, phases.UNSCOPED: 1.0}),
    (10.0, 11.0, {FILL: 0.0, TB: 0.0, phases.UNSCOPED: 0.0})])
def test_nested_ops_count_each_instant_once(lo, hi, want):
    got = phases.split({"dev": OPS}, lo, hi)
    assert got == pytest.approx(want)
    busy = devtrace.reduce(
        {"dev": devtrace.DeviceEvents(ops=[("op", s, e) for _, s, e in OPS],
                                      modules=[])}, lo, hi)["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)


def test_a_scope_comes_from_the_compiled_text():
    """Programs that share a module name share a map; where they
    disagree, the instruction maps to no phase."""
    hlo = phases.hlo_scopes([
        'HloModule jit_fn, is_scheduled=true\n'
        '  %while.3 = s32[] while(s32[] %p), metadata={op_name='
        '"jit(fn)/plan.traceback/while"}\n'
        '  ROOT %copy.1 = s32[] copy(%while.3), metadata={op_name='
        '"jit(fn)/plan.fill/copy"}',
        'HloModule jit_fn\n'
        '  %copy.1 = s32[] copy(%x), metadata={op_name="jit(fn)/plan.'
        'traceback/copy"}',
        'HloModule jit_g\n'
        '  %fusion.2 = s32[] fusion(%x), metadata={op_name="jit(g)/plan.'
        'fill/vmap(while)/body/add"}'])
    assert hlo == {("jit_fn", "while.3"): TB, ("jit_fn", "copy.1"): None,
                   ("jit_g", "fusion.2"): FILL}
    ops = phases.scoped(
        [Event("%fusion.2 = s32[] fusion(...)", 1000, 10),
         Event("%while.3 = (s32[]) while(...)", 6000, 10),
         Event("copy.1", 3000, 10, (("hlo_op", "copy.1"),
                                     ("hlo_module", "jit_fn"))),
         Event("add.9", 9000, 10)],
        [Event("jit_g(7)", 0, 5000), Event("jit_fn(8)", 5000, 2000)], hlo)
    assert [p for p, _, _ in ops] == [FILL, None, TB, None]
    with pytest.raises(ValueError, match="HLO module"):
        phases.hlo_scopes(["not hlo"])


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture
def root(tmp_path):
    """A tiny closed-loop cell reporting the harvested-cell share."""
    shutil.copytree(REPO / "bench" / "metrics", tmp_path / "metrics")
    _write(tmp_path / "configs" / "tiny_local.json", {
        "source": "test", "kernel": "local_affine",
        "service": {"max_len": 32, "block": 8, "max_block": 8},
        "scoring": {"match": 2, "mismatch": -4, "gap_open": -6,
                    "gap_extend": -2}, "check_sample": 12})
    _write(tmp_path / "traffic" / "tiny_closed.json", {
        "loop": "closed", "backlog": 16, "pool": 48, "shape_seed": 2,
        "reads": {"length": {"uniform": [12, 30]}, "window_extra": [0, 2],
                  "divergence": 0.05, "error_mix": [0.6, 0.2, 0.2]}})
    _write(tmp_path / "BENCHMARK.json", {
        "workloads": [{"name": "tiny.closed", "config": "tiny_local",
                       "traffic": "tiny_closed", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "req_per_s", "unit": "req/s",
                        "better": "higher", "bound": 0.25,
                        "source": "host_clock"}],
        "per_layer": [{"name": "batching.harvested_cell_share.offline",
                       "unit": "%", "better": "higher",
                       "source": "program_span", "layer": "x",
                       "moves": "req_per_s"}]})
    return tmp_path


@pytest.fixture
def _restored():
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    was = {n: getattr(jax.config, n) for n in names}
    obs_trace.enable_jax_bridge()
    yield
    obs_trace.disable_jax_bridge()
    for n, v in was.items():
        jax.config.update(n, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def test_a_traced_run_splits_its_device_time(root, monkeypatch, _restored):
    """A traced run with the bridge on: the harvested-cell share, the
    fill and traceback shares of busy time (CPU ops stand in for the
    device; their scopes come from the plans' compiled text) and the
    spans' own profiler events, paired by ``span_id``."""
    from repro.runtime import plan as plan_mod

    cap = {}

    def cpu_events(pd):
        return [ev for plane in pd.planes if plane.name == "/host:CPU"
                for line in plane.lines for ev in line.events
                if any(k == "hlo_op" for k, _ in ev.stats)]

    def cpu_ops(pd):
        cap["pd"] = pd
        ops = [(ev.name, ev.start_ns * 1e-9,
                (ev.start_ns + ev.duration_ns) * 1e-9)
               for ev in cpu_events(pd)]
        return {"cpu": devtrace.DeviceEvents(ops=ops, modules=ops)}

    def host_timeline(spans, offset, _orig=devtrace.host_timeline):
        cap["offset"] = offset
        return _orig(spans, offset)

    def reduce(devices, lo, hi, timeline=(), top=10, _orig=devtrace.reduce):
        cap["lo"], cap["hi"] = lo, hi
        return _orig(devices, lo, hi, timeline, top)

    def build_service(cfg, _orig=harness.build_service):
        cap["svc"] = _orig(cfg)
        return cap["svc"]

    monkeypatch.setattr(devtrace, "tpu_devices", cpu_ops)
    monkeypatch.setattr(devtrace, "host_timeline", host_timeline)
    monkeypatch.setattr(devtrace, "reduce", reduce)
    monkeypatch.setattr(harness, "build_service", build_service)
    monkeypatch.setattr(harness, "TRACE_S", 0.6)
    res = harness.run_cell("tiny.closed", BIG_SEED, 1.5, True,
                           t_start=time.monotonic(), require_tpu=False,
                           repo=root, root=root, out=io.StringIO(),
                           err=io.StringIO())
    assert res["correct"] is True
    share = res["metrics"]["batching.harvested_cell_share.offline"]["value"]
    assert 0 < share <= 100

    svc, pd = cap["svc"], cap["pd"]
    spec, params, _ = svc.channels["local_affine"]
    texts = [plan_mod.get_plan(spec, svc.engine_name, (qb,), (rb,),
                               batch_size=svc.block_for("local_affine",
                                                        (qb, rb))
                               ).compiled_text()
             for qb, rb in {d["bucket"] for d in svc.dispatches}]
    ops = phases.scoped(cpu_events(pd), (), phases.hlo_scopes(texts))
    split = phases.split({"cpu": ops}, cap["lo"], cap["hi"])
    busy = res["device"]["busy_s"]
    assert sum(split.values()) == pytest.approx(busy, rel=1e-9)
    fill, tb = (100 * split[p] / busy for p in phases.PHASES)
    assert 0 < fill <= 100 and 0 < tb <= 100 and fill + tb <= 100

    host = phases.profiler_spans(pd)
    spans = [s for s in obs_trace.spans() if s.t1 is not None]
    paired = [s for s in spans if s.args["span_id"] in host]
    assert paired and all(host[s.args["span_id"]][0] == s.name
                          for s in paired)
    skew = phases.clock_skew_us(spans, cap["offset"], host)
    assert 0 <= skew < 50_000


@pytest.mark.parametrize("args,want", [
    ([{"cells_useful": 30, "cells_launched": 64},
      {"cells_useful": 18, "cells_launched": 32}], 50.0),
    ([{"n": 8, "done": 8}], None)])
def test_harvested_cell_share_reads_the_harvest_spans(args, want):
    """Cells come from the harvest spans alone; a program whose spans
    carry no cell counts gives nothing."""
    span = obs_trace.Span
    ctx = harness.Context(
        cell="c", kernel="k", window=None, trace=None, device_kind="cpu",
        block_for=None,
        spans=[span("gw.form", "g", 0.0, 1.0, "t", {"bucket": [8, 8]})]
        + [span("gw.harvest", "g", 1.0 + k, 2.0 + k, "t", a)
           for k, a in enumerate(args)])
    got = harness.reader("batching.harvested_cell_share.offline")(ctx)
    assert got == (pytest.approx(want) if want is not None else None)
