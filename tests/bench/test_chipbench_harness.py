"""The benchmark harness on the CPU, through its library functions, at a
size a test run holds: a tiny test-only configuration and mix in a
temporary copy of the benchmark's layout.  The harness's look for a TPU
is switched off here and only here."""
from __future__ import annotations

import dataclasses
import io
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "check"]
BIG_SEED = 2**31 + 977


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


READS = {"length": {"uniform": [20, 30]}, "window_extra": [0, 2],
         "divergence": 0.05, "error_mix": [0.6, 0.2, 0.2]}


@pytest.fixture
def root(tmp_path):
    """A benchmark root: the real metric readers, tiny configurations
    and mixes, and a manifest naming them."""
    shutil.copytree(REPO / "bench" / "metrics", tmp_path / "metrics")
    svc = {"max_len": 32, "block": 8, "max_block": 8}
    _write(tmp_path / "configs" / "tiny_global.json", {
        "source": "test", "kernel": "global_affine", "service": svc,
        "scoring": {"match": 1, "mismatch": -4, "gap_open": -7,
                    "gap_extend": -1}, "check_sample": 12})
    _write(tmp_path / "configs" / "tiny_local.json", {
        "source": "test", "kernel": "local_affine", "service": svc,
        "scoring": {"match": 2, "mismatch": -4, "gap_open": -6,
                    "gap_extend": -2}, "check_sample": 12})
    _write(tmp_path / "traffic" / "tiny_open.json", {
        "loop": "open", "rate_per_s": 100, "shape_seed": 1, "reads": READS})
    _write(tmp_path / "traffic" / "tiny_closed.json", {
        "loop": "closed", "backlog": 16, "pool": 48, "shape_seed": 2,
        "reads": READS})
    man = {
        "workloads": [
            {"name": "tiny.open", "config": "tiny_global",
             "traffic": "tiny_open", "chips": 1, "why": "test"},
            {"name": "tiny.closed", "config": "tiny_local",
             "traffic": "tiny_closed", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "req_per_s", "unit": "req/s", "better": "higher",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["tiny.closed"]},
            {"name": "p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["tiny.open"]}],
        "per_layer": [
            {"name": "gateway.host_ms_per_batch.offline", "unit": "ms",
             "better": "lower", "source": "program_span",
             "layer": "gateway", "moves": "req_per_s"}]}
    _write(tmp_path / "BENCHMARK.json", man)
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


def run(root: Path, cell: str, seconds: float = 1.0):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(cell, BIG_SEED, seconds, False,
                           t_start=time.monotonic(), require_tpu=False,
                           repo=root, root=root, out=out, err=err)
    return res, out.getvalue().splitlines(), err.getvalue().splitlines()


@pytest.mark.parametrize("cell,e2e", [("tiny.open", ["setup_s", "p50_ms"]),
                                      ("tiny.closed",
                                       ["setup_s", "req_per_s"])])
def test_last_line_has_exactly_the_contract_keys(root, cell, e2e):
    res, out, err = run(root, cell)
    last = json.loads(out[-1])
    assert list(last) == CONTRACT_KEYS
    assert last == json.loads(json.dumps(res))
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert sorted(last["metrics"]) == sorted(e2e)
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # every number compared, with its limit, last on stderr and last in
    # the line
    assert err[-3:] == [f"check {k} {v['value']} limit {v['limit']}"
                        for k, v in last["check"].items()]
    info = json.loads(out[0])["info"]
    assert info["compiles_in_window"] == 0
    assert info["plan_compiles_in_window"] == 0


def test_no_tpu_means_no_result(capsys):
    """On the CPU the command exits 1 and prints no result line."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  REPO / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--workload", "long_ont.offline", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 1
    assert out.out.strip() == ""
    assert "no result" in out.err


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's own paths
    exits nonzero and prints no result."""
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in man["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src")
    cmd = man["command"] + ["--workload", man["workloads"][0]["name"],
                            "--seed", "1", "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_new_files_are_found_by_name(root):
    """A deployment, a mix, an end-to-end percentile and a per-layer
    reader are added as files and manifest entries alone."""
    _write(root / "configs" / "tiny_new.json", dict(
        json.loads((root / "configs" / "tiny_global.json").read_text()),
        source="another test deployment"))
    _write(root / "traffic" / "tiny_burst.json", {
        "loop": "open", "rate_per_s": 150, "shape_seed": 9, "reads": READS})
    (root / "metrics" / "extra.spans.py").write_text(
        "def read(ctx):\n    return float(len(ctx.spans)) or None\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "tiny_new.burst", "config": "tiny_new",
                             "traffic": "tiny_burst", "chips": 1,
                             "why": "test"})
    man["end_to_end"].append({"name": "p99_ms", "unit": "ms",
                              "better": "lower", "bound": 0.25,
                              "source": "host_clock",
                              "workloads": ["tiny_new.burst"]})
    man["per_layer"].append({"name": "extra.spans.burst", "unit": "1",
                             "better": "lower", "source": "program_span",
                             "layer": "gateway", "moves": "p99_ms",
                             "workloads": ["tiny_new.burst"]})
    _write(root / "BENCHMARK.json", man)
    assert harness.reader_path("extra.spans.burst", root).name == \
        "extra.spans.py"
    e2e, layer = harness.cell_metrics(man, "tiny_new.burst")
    assert [m["name"] for m in e2e] == ["setup_s", "p99_ms"]
    assert [m["name"] for m in layer] == ["extra.spans.burst"]
    res, _, _ = run(root, "tiny_new.burst")
    assert res["correct"] and set(res["metrics"]) == {"setup_s", "p99_ms"}


# -- the timed path broken underneath: correct must come out false ---------
def _broken_engine(kind):
    import jax.numpy as jnp
    from repro.runtime import registry

    ref = registry.get_engine("reference")

    def engine(spec, params, query, ref_seq, q_len=None, r_len=None):
        res = ref(spec, params, query, ref_seq, q_len, r_len)
        if kind == "score":        # an answer altered where it is produced
            return dataclasses.replace(res, score=res.score + 1)
        # a path altered: every pointer reads END, the walk stops at once
        return dataclasses.replace(res, tb=jnp.zeros_like(res.tb))
    return engine


@pytest.mark.parametrize("fault,number", [("score", "score_or_end_wrong"),
                                          ("path", "path_wrong"),
                                          ("dropped", "unanswered")])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault,
                                            number):
    from repro.runtime import registry
    from repro.serve.gateway import FaultPlan

    cfg_path = root / "configs" / "tiny_global.json"
    cfg = json.loads(cfg_path.read_text())
    if fault == "dropped":
        # from the window on, half of the batches fail their harvest and
        # are not retried: their answers never come
        cfg["service"]["max_retries"] = 0
        window = harness.run_window

        def broken_window(svc, *a, **kw):
            svc.fault_plan = FaultPlan(seed=3, fail_harvest_p=0.5)
            return window(svc, *a, **kw)
        monkeypatch.setattr(harness, "run_window", broken_window)
    else:
        name = f"bench_fault_{fault}"
        registry.register_engine(name, fn=_broken_engine(fault),
                                 overwrite=True)
        monkeypatch.setattr(registry, "_REGISTRY", dict(registry._REGISTRY))
        cfg["service"]["engine_name"] = name
    _write(cfg_path, cfg)
    res, _, err = run(root, "tiny.open", seconds=0.5)
    assert res["correct"] is False
    assert res["check"][number]["value"] > res["check"][number]["limit"]
    assert any(line.startswith(f"check {number} ") for line in err)


def test_the_control_through_the_harness_is_not_correct(root):
    """``--control-bits 8``: the reference saturated to 8 bits takes the
    service's place in a whole run, on reads whose scores pass 127."""
    _write(root / "configs" / "tiny_long.json", {
        "source": "test", "kernel": "local_affine",
        "service": {"max_len": 128, "block": 8, "max_block": 8},
        "scoring": {"match": 2, "mismatch": -4, "gap_open": -6,
                    "gap_extend": -2}, "check_sample": 8})
    _write(root / "traffic" / "tiny_long.json", {
        "loop": "closed", "backlog": 8, "pool": 16, "shape_seed": 3,
        "reads": {"length": {"uniform": [90, 100]}, "window_extra": [0, 0],
                  "divergence": 0.02, "error_mix": [0.6, 0.2, 0.2]}})
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "tiny_long.closed",
                             "config": "tiny_long", "traffic": "tiny_long",
                             "chips": 1, "why": "test"})
    man["end_to_end"][1]["workloads"].append("tiny_long.closed")
    _write(root / "BENCHMARK.json", man)
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell("tiny_long.closed", BIG_SEED, 0.5, False,
                           t_start=time.monotonic(), require_tpu=False,
                           repo=root, root=root, out=out, err=err,
                           control_bits=8)
    assert res["correct"] is False
    assert res["check"]["score_or_end_wrong"]["value"] > 0
    assert res["check"]["unanswered"]["value"] == 0
    served, _, _ = run(root, "tiny_long.closed", seconds=0.5)
    assert served["correct"] is True


def test_a_traced_run_reads_its_slice(root, monkeypatch):
    """``--trace 1``: the profiler covers a slice in the middle of the
    window, the per-layer readers see that slice, and the line carries
    the device's busy time and the breakdown.  On the CPU the host's XLA
    ops stand in for a device plane."""
    from bench import devtrace

    def cpu_ops(pd):
        ops = [(ev.name, ev.start_ns * 1e-9,
                (ev.start_ns + ev.duration_ns) * 1e-9)
               for plane in pd.planes if plane.name == "/host:CPU"
               for line in plane.lines for ev in line.events
               if any(k == "hlo_op" for k, _ in ev.stats)]
        return {"cpu": devtrace.DeviceEvents(ops=ops, modules=ops)}
    monkeypatch.setattr(devtrace, "tpu_devices", cpu_ops)
    monkeypatch.setattr(harness, "TRACE_S", 0.6)
    man = json.loads((root / "BENCHMARK.json").read_text())
    for name, unit in [("batching.useful_cell_share.offline", "%"),
                       ("device.idle_share.offline", "%")]:
        man["per_layer"].append({"name": name, "unit": unit,
                                 "better": "lower", "source": "device_trace",
                                 "layer": "x", "moves": "req_per_s"})
    _write(root / "BENCHMARK.json", man)
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell("tiny.closed", BIG_SEED, 1.5, True,
                           t_start=time.monotonic(), require_tpu=False,
                           repo=root, root=root, out=out, err=err)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"gateway.host_ms_per_batch.offline",
                                   "batching.useful_cell_share.offline",
                                   "device.idle_share.offline"}
    assert 0 < res["metrics"]["batching.useful_cell_share.offline"][
        "value"] <= 100
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"] <= 0.7
    assert list(res["breakdown"]) == ["device_ops", "idle_gaps"]
    info = json.loads(out.getvalue().splitlines()[0])["info"]
    assert info["trace"]["ended_modules"] > 0
