"""Trace reduction (bench/devtrace.py): interval algebra, and a small
trace recorded on the CPU read through the same code."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import devtrace  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402


def test_timeline_names_the_innermost_span():
    span = obs_trace.Span
    spans = [span("a", "g", 0.0, 10.0, "t", None),
             span("b", "g", 2.0, 4.0, "t", None),
             span("c", "g", 3.0, 3.5, "t", None),
             span("d", "g", 12.0, 13.0, "t", None)]
    tl = devtrace.host_timeline(spans, offset=1.0)
    assert tl == [(1.0, 3.0, "a"), (3.0, 4.0, "b"), (4.0, 4.5, "c"),
                  (4.5, 5.0, "b"), (5.0, 11.0, "a"), (13.0, 14.0, "d")]
    assert devtrace.label_time(tl, 10.0, 14.0) == pytest.approx(
        {"a": 1.0, "d": 1.0, "no host span": 2.0})


def test_union_clip_gaps():
    busy = devtrace.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 12)])
    assert busy == [(0, 3), (5, 7), (9, 12)]
    assert devtrace.clip(busy, 1, 10) == [(1, 3), (5, 7), (9, 10)]
    assert devtrace.gaps(devtrace.clip(busy, 1, 10), 1, 10) == [(3, 5),
                                                                (7, 9)]
    assert devtrace.total([(1, 3), (5, 7)]) == 4


def test_reduce_on_synthetic_device():
    span = obs_trace.Span
    spans = [span("gw.harvest", "g", 0.0, 4.0, "t", None),
             span("gw.launch", "g", 4.0, 5.0, "t", None),
             span("plan.compile", "g", 4.2, 4.3, "t", None)]
    dev = devtrace.DeviceEvents(
        ops=[("fusion.1", 0.5, 3.0), ("fusion.2", 3.0, 3.5),
             ("fusion.1", 5.0, 6.0)],
        modules=[("jit_fn", 0.4, 3.6), ("jit_fn", 4.9, 6.1)])
    red = devtrace.reduce({"/device:TPU:0": dev}, 0.0, 6.0,
                          devtrace.host_timeline(spans, offset=0.0))
    assert red["busy_s"] == pytest.approx(4.0)
    assert red["module_s"] == pytest.approx(3.2 + 1.1)
    assert red["ended_module_s"] == pytest.approx(3.2)  # 2nd ends at 6.1
    ops = dict(map(tuple, red["breakdown"]["device_ops"]))
    assert ops == pytest.approx({"fusion.1": 3.5, "fusion.2": 0.5})
    idle = dict(map(tuple, red["breakdown"]["idle_gaps"]))
    # 0-0.5 and 3.5-4 inside gw.harvest, 4-5 inside gw.launch but for
    # the 0.1 s of plan.compile nested in it
    assert idle == pytest.approx({"gw.harvest": 1.0, "gw.launch": 0.9,
                                  "plan.compile": 0.1})


def test_a_trace_recorded_on_cpu(tmp_path):
    """The harness's steps on a real ``.xplane.pb``: the window
    annotation, the clock offset to ``repro.obs`` spans, and the
    reduction (CPU ops stand in for a device plane)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs_trace.clear()
    obs_trace.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        mono = time.monotonic()
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            for _ in range(3):
                with obs_trace.span("gw.launch", cat="gateway"):
                    y = f(x)
                with obs_trace.span("gw.harvest", cat="gateway"):
                    y.block_until_ready()
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
        obs_trace.disable()
    pd = devtrace.load(devtrace.find_xplane(str(tmp_path)))
    lo, hi = devtrace.host_annotation(pd, devtrace.WINDOW)
    assert hi - lo >= 0.03
    ops = [(ev.name, ev.start_ns * 1e-9,
            (ev.start_ns + ev.duration_ns) * 1e-9)
           for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events
           if "hlo_op" in dict(ev.stats)]
    assert ops, "the CPU trace holds no XLA op"
    dev = devtrace.DeviceEvents(ops=ops, modules=ops)
    spans = obs_trace.spans()
    red = devtrace.reduce({"cpu": dev}, lo, hi,
                          devtrace.host_timeline(spans, lo - mono))
    assert 0.0 < red["busy_s"] < red["window_s"]
    names = {k for k, _ in red["breakdown"]["idle_gaps"]}
    assert names & {"gw.launch", "gw.harvest", "no host span"}
    assert devtrace.tpu_devices(pd) == {}
