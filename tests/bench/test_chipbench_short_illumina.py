"""The ``short_illumina`` deployment and its ``short_illumina.offline``
cell on the CPU: the real files load and bucket as the cell says, the
reader of ``gateway.host_us_per_row`` works on spans alone, and a tiny
short-read-shaped cell (``global_affine`` over three buckets, small
blocks) runs through the harness untraced and traced."""
from __future__ import annotations

import collections
import io
import json
import shutil
import sys
import time
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness, traffic  # noqa: E402

BIG_SEED = 2**31 + 4099
CELL = "short_illumina.offline"
HOST_METRIC = "gateway.host_us_per_row.offline"


# -- the real files ----------------------------------------------------------
def test_the_cell_and_its_files_are_in_the_manifest():
    man = harness.manifest()
    w = harness.workload(man, CELL)
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("short_illumina", "illumina_batch", 1)
    cfg = next(c for c in man["configs"] if c["name"] == "short_illumina")
    assert cfg["source"] == harness.config("short_illumina")["source"]
    assert cfg["reduced"] == list(harness.config("short_illumina")["reduced"])
    e2e, layer = harness.cell_metrics(man, CELL)
    assert [m["name"] for m in e2e] == ["setup_s", "req_per_s"]
    assert {m["name"] for m in layer} == {
        "gateway.host_ms_per_batch.offline", "device.idle_share.offline",
        "batching.harvested_cell_share.offline", HOST_METRIC}
    assert HOST_METRIC in {m["name"] for m in
                           harness.cell_metrics(man, "long_ont.offline")[1]}
    assert harness.reader_path(HOST_METRIC).name == \
        "gateway.host_us_per_row.py"


def test_the_mix_falls_into_three_buckets():
    """The pool of ``illumina_batch`` under the service of
    ``short_illumina``: 128 x 128, 128 x 256 and 256 x 256, at about
    8.6, 48.5 and 42.9% of the requests, each at a 256-row block."""
    from repro.runtime import bucketing

    cfg, mx = harness.config("short_illumina"), harness.mix("illumina_batch")
    assert (mx["loop"], mx["backlog"], mx["pool"]) == ("closed", 4096, 65536)
    assert mx["reads"] == harness.mix("illumina_closed")["reads"]
    svc = harness.build_service(cfg)
    reads, windows, _ = traffic.shapes(mx, traffic.request_count(mx, 30))
    assert len(reads) == 65536
    count = collections.Counter(
        bucketing.bucket_shape(int(q), int(r), min_bucket=svc.min_bucket,
                               max_bucket=svc.max_bucket)
        for q, r in zip(reads, windows))
    share = {b: 100.0 * n / len(reads) for b, n in count.items()}
    want = {(128, 128): 8.6, (128, 256): 48.5, (256, 256): 42.9}
    assert set(share) == set(want)
    assert all(abs(share[b] - want[b]) < 2.0 for b in want)
    assert all(svc.block_for(cfg["kernel"], b) == 256 for b in want)


# -- the reader alone ------------------------------------------------------
def _span(name, t0, t1, **args):
    from repro.obs.trace import Span
    return Span(name, "gateway", t0, t1, "bench-dispatch", args or None)


def test_the_reader_gives_nothing_without_land_spans():
    read = harness.reader(HOST_METRIC)
    parent_like = [_span("gw.form", 0.0, 0.001, n=4),
                   _span("gw.launch", 0.001, 0.003, n=4),
                   _span("gw.harvest", 0.003, 0.010, n=4, done=4)]
    assert read(types.SimpleNamespace(spans=parent_like)) is None
    assert read(types.SimpleNamespace(spans=[])) is None


def test_the_reader_divides_host_time_by_rows_landed():
    read = harness.reader(HOST_METRIC)
    spans = []
    for k in range(2):            # two batches of 4 rows, 10 ms apart
        t = 0.01 * k
        spans += [_span("gw.form", t, t + 0.001, n=4),
                  _span("gw.launch", t + 0.001, t + 0.003, n=4),
                  _span("gw.pad", t + 0.001, t + 0.002, n=4),
                  _span("gw.harvest", t + 0.003, t + 0.009, n=4),
                  _span("gw.materialize", t + 0.003, t + 0.005),
                  _span("gw.land", t + 0.005, t + 0.009, n=4)]
    v = read(types.SimpleNamespace(spans=spans))
    # (1 + 2 + 4 ms) x 2 over 8 rows; pad 1 ms and land 4 ms x 2 over 8
    assert v["value"] == pytest.approx(1750.0)
    assert v["pad_us"] == pytest.approx(250.0)
    assert v["land_us"] == pytest.approx(1000.0)


# -- a tiny short-read cell through the harness ---------------------------
READS = {"length": {"uniform": [14, 20]}, "window_extra": [0, 12],
         "divergence": 0.01, "error_mix": [0.90, 0.05, 0.05]}
TINY = "tiny_short.offline"


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture
def root(tmp_path):
    """The real readers; a configuration shaped like ``short_illumina``
    (BWA-MEM scoring, ``global_affine``, blocks sized by a traceback
    budget, capped at 8 rows) over reads of 14-20 bases against windows
    up to 12 longer: buckets 16 x 16, 16 x 32 and 32 x 32."""
    shutil.copytree(REPO / "bench" / "metrics", tmp_path / "metrics")
    real = harness.config("short_illumina")
    _write(tmp_path / "configs" / "tiny_short.json", dict(
        real, source="test", check_sample=24,
        service={"max_len": 32, "tb_budget_bytes": 1 << 20, "block": 4,
                 "max_block": 8}))
    _write(tmp_path / "traffic" / "tiny_batch.json", {
        "loop": "closed", "backlog": 48, "pool": 192, "shape_seed": 12,
        "reads": READS})
    layer = ["gateway.host_ms_per_batch.offline", "device.idle_share.offline",
             "batching.harvested_cell_share.offline", HOST_METRIC]
    _write(tmp_path / "BENCHMARK.json", {
        "workloads": [{"name": TINY, "config": "tiny_short",
                       "traffic": "tiny_batch", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "req_per_s", "unit": "req/s", "better": "higher",
             "bound": 0.1, "source": "host_clock", "workloads": [TINY]}],
        "per_layer": [{"name": n, "unit": "x", "better": "lower",
                       "source": "program_span", "layer": "gateway",
                       "moves": "req_per_s", "workloads": [TINY]}
                      for n in layer]})
    return tmp_path


@pytest.fixture(autouse=True)
def _jax_config_restored():
    """A run points JAX's compile cache at the checkout; put the
    process's settings back for the tests that follow in this worker."""
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def _cpu_ops(pd):
    """On the CPU the host's XLA ops stand in for a device plane."""
    from bench import devtrace
    ops = [(ev.name, ev.start_ns * 1e-9,
            (ev.start_ns + ev.duration_ns) * 1e-9)
           for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events
           if any(k == "hlo_op" for k, _ in ev.stats)]
    return {"cpu": devtrace.DeviceEvents(ops=ops, modules=ops)}


@pytest.mark.parametrize("traced", [False, True])
def test_a_short_read_cell_runs_through_the_harness(root, monkeypatch,
                                                    traced):
    from bench import devtrace

    monkeypatch.setattr(devtrace, "tpu_devices", _cpu_ops)
    monkeypatch.setattr(harness, "TRACE_S", 0.6)
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(TINY, BIG_SEED, 1.5 if traced else 1.0, traced,
                           t_start=time.monotonic(), require_tpu=False,
                           repo=root, root=root, out=out, err=err)
    info = json.loads(out.getvalue().splitlines()[0])["info"]
    assert sorted(map(tuple, info["warm"]["buckets"])) == \
        [(16, 16), (16, 32), (32, 32)]
    assert info["warm"]["blocks"] == [8, 8, 8]
    assert info["plan_compiles_in_window"] == 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"]["unanswered"]["value"] == 0
    metrics = res["metrics"]
    if not traced:
        assert set(metrics) == {"setup_s", "req_per_s"}
        assert metrics["req_per_s"]["value"] > 0
        return
    host = metrics[HOST_METRIC]
    assert set(host) == {"value", "pad_us", "land_us", "unit"}
    assert 0 < host["pad_us"] and 0 < host["land_us"] < host["value"]
    assert 0 < metrics["batching.harvested_cell_share.offline"][
        "value"] <= 100
    assert {"gateway.host_ms_per_batch.offline",
            "device.idle_share.offline"} <= set(metrics)
