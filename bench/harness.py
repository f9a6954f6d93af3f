"""One benchmark cell per run: a deployment under a traffic mix, served
through ``AlignmentService.submit`` and measured from the client's side.

Everything is found by name.  ``BENCHMARK.json`` names the cell; the cell
names its configuration (``bench/configs/<config>.json``) and its mix
(``bench/traffic/<traffic>.json``); each per-layer metric is read by
``bench/metrics/<prefix>.py``, the longest dotted prefix of its name that
has a file (``gateway.host_ms_per_batch.offline`` is read by
``gateway.host_ms_per_batch.py``).  A new deployment, mix or metric is a
new file and a new manifest entry.

A run: set-up (device check, compile cache, traffic from the seed, the
service with the configuration's scoring, its plans warmed and one
warm-up batch per bucket), then a window of ``--seconds`` in which a
client thread submits (on the mix's schedule, or keeping a backlog) while
one dispatcher thread drives the service, then the check of what the
window produced against the plain reference, then one JSON line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

POLL_S = 0.0005          # client poll period for completions, open loop
CLOSED_POLL_S = 0.002    # closed loop: it only tops up its backlog
IDLE_S = 0.0002          # dispatcher sleep when every queue is empty
GRACE_S = 60.0           # how long past the window answers are awaited
SWITCH_S = 0.0005        # interpreter switch interval: client and server
                         # threads share the interpreter lock
TRACE_S = 4.0            # the profiler covers this much of the middle
                         # of a traced window, where the loop is steady: a
                         # trace holds every op of every loop step, and
                         # must be read inside the run


class NoAccelerator(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


# -- finding things by name --------------------------------------------------
def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(repo: Path = REPO) -> dict:
    return _json(repo / "BENCHMARK.json")


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r}; have "
                   f"{[w['name'] for w in man['workloads']]}")


def config(name: str, root: Path = BENCH) -> dict:
    return _json(root / "configs" / f"{name}.json")


def mix(name: str, root: Path = BENCH) -> dict:
    return _json(root / "traffic" / f"{name}.json")


def reader_path(metric: str, root: Path = BENCH) -> Path:
    parts = metric.split(".")
    for k in range(len(parts), 0, -1):
        p = root / "metrics" / (".".join(parts[:k]) + ".py")
        if p.is_file():
            return p
    raise FileNotFoundError(f"no reader for metric {metric!r} under "
                            f"{root / 'metrics'}")


def reader(metric: str, root: Path = BENCH) -> Callable:
    path = reader_path(metric, root)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(man: dict, cell: str):
    """The end-to-end and per-layer metric entries a cell reports."""
    e2e = [m for m in man["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"]
             if cell in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


# -- the run -------------------------------------------------------------------
@dataclasses.dataclass
class Window:
    """What the client saw.  Times are ``time.monotonic()`` seconds."""
    t0: float
    t1: float
    pairs: List                      # (query, ref) of each submission
    due: np.ndarray                  # when each was due (sent, if closed)
    sent: np.ndarray
    done: np.ndarray                 # when the client saw it resolved
    futures: List
    lateness_s: np.ndarray           # sent - due (open loop)
    errors: List[str]

    def ok(self) -> np.ndarray:
        return np.array([f.done() and "failed" not in f.req.result
                         and "cigar" in f.req.result for f in self.futures],
                        bool)


def device_check(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX has "
                            f"{len(devs)}")
    return devs[:chips]


def build_service(cfg: dict):
    """The service as the configuration states it, with its scoring."""
    import jax.numpy as jnp
    from repro.core import kernels_zoo
    from repro.serve.alignment_service import AlignmentService

    svc = AlignmentService(**cfg["service"])
    spec, _ = kernels_zoo.make(cfg["kernel"])
    params = {k: jnp.int32(v) for k, v in cfg["scoring"].items()}
    svc.channels[cfg["kernel"]] = (spec, params, None)
    return svc


def warm(svc, kernel: str, pairs) -> dict:
    """Compile the cell's own plans, then serve one batch per bucket so
    the host path (padding, landing, the first transfers) is warm too."""
    from repro.runtime import bucketing
    from repro.serve.alignment_service import AlignRequest

    by_bucket: Dict[tuple, list] = {}
    for q, r in pairs:
        b = bucketing.bucket_shape(len(q), len(r), min_bucket=svc.min_bucket,
                                   max_bucket=svc.max_bucket)
        batch = by_bucket.setdefault(b, [])
        if len(batch) < svc.block_for(kernel, b):
            batch.append((q, r))
    bks = sorted(by_bucket)
    plans = svc.warm([(kernel, b, svc.block_for(kernel, b)) for b in bks])
    for b in bks:                  # one bucket at a time: no coalescing
        futs = [svc.submit(AlignRequest(rid=-1 - k, kernel=kernel, query=q,
                                        ref=r))
                for k, (q, r) in enumerate(by_bucket[b])]
        svc.wait(futs)
    return {"plans": plans, "buckets": [list(b) for b in bks],
            "blocks": [svc.block_for(kernel, b) for b in bks]}


def _dispatcher(svc, stop: threading.Event, errors: List[str]) -> None:
    while not stop.is_set():
        try:
            n = svc.drain()
        except Exception as exc:       # the gateway requeued the batch
            errors.append(f"{type(exc).__name__}: {exc}")
            continue
        if not n:
            time.sleep(IDLE_S)


def _resolved(svc) -> int:
    st = svc.stats
    return (st["completed"] + st["dead_lettered"] + st["filtered"]
            + st["degraded"])


def run_window(svc, kernel: str, tr, mx: dict, seconds: float,
               backlog: int, at: Sequence[tuple] = ()) -> Window:
    """Drive the service for ``seconds``: the open loop submits each
    request when it is due, the closed loop keeps ``backlog`` requests
    outstanding (cycling through the traffic).  Answers still owed at
    the close are awaited up to ``GRACE_S``.  Each ``(t, fn)`` of ``at``
    runs ``fn`` once, ``t`` seconds into the window."""
    from repro.serve.alignment_service import AlignRequest

    open_loop = mx["loop"] == "open"
    n_src = len(tr)
    cap = n_src if open_loop else max(n_src, 1) * 64
    due = np.full(cap, np.nan)
    sent = np.full(cap, np.nan)
    done = np.full(cap, np.nan)
    futures: List = []
    pairs: List = []
    pending: List[int] = []
    errors: List[str] = []
    stop = threading.Event()
    th = threading.Thread(target=_dispatcher, args=(svc, stop, errors),
                          name="bench-dispatch", daemon=True)
    clock = time.monotonic

    def submit(k: int, when: float) -> None:
        q, r = tr.queries[k % n_src], tr.refs[k % n_src]
        futures.append(svc.submit(AlignRequest(rid=k, kernel=kernel,
                                               query=q, ref=r)))
        pairs.append((q, r))
        due[k] = when
        sent[k] = clock()
        pending.append(k)

    seen = -1

    def collect(now: float) -> None:
        nonlocal seen, pending
        r = _resolved(svc)
        if r == seen:
            return
        seen = r
        still = []
        for k in pending:
            if futures[k].done():
                done[k] = now
            else:
                still.append(k)
        pending = still

    old_switch = sys.getswitchinterval()
    sys.setswitchinterval(SWITCH_S)
    todo = sorted(at, key=lambda e: e[0])
    th.start()
    try:
        t0 = clock()
        t1 = t0 + seconds
        k = 0
        if not open_loop:
            for _ in range(backlog):
                submit(k, clock())
                k += 1
        while True:
            now = clock()
            if now >= t1:
                break
            while todo and now >= t0 + todo[0][0]:
                todo.pop(0)[1]()
            if open_loop:
                while k < n_src and t0 + tr.arrivals[k] <= now:
                    submit(k, t0 + tr.arrivals[k])
                    k += 1
            collect(now)
            if not open_loop:
                while len(pending) < backlog and k < cap:
                    submit(k, clock())
                    k += 1
            nxt = now + (POLL_S if open_loop else CLOSED_POLL_S)
            if open_loop and k < n_src:
                nxt = min(nxt, t0 + tr.arrivals[k])
            time.sleep(max(0.0, nxt - clock()))
        t_close = clock()
        while pending and clock() - t_close < GRACE_S:
            collect(clock())
            time.sleep(POLL_S)
    finally:
        stop.set()
        th.join(timeout=GRACE_S)
        sys.setswitchinterval(old_switch)
    if th.is_alive():
        raise RuntimeError("the dispatcher thread did not stop")
    n = len(futures)
    return Window(t0=t0, t1=t1, pairs=pairs, due=due[:n], sent=sent[:n],
                  done=done[:n], futures=futures,
                  lateness_s=(sent[:n] - due[:n]) if open_loop
                  else np.zeros(n), errors=errors)


def check(win: Window, cfg: dict, seed: int,
          control_bits: Optional[int] = None) -> dict:
    """Every due request must be answered; a sample drawn from the seed,
    the largest among them, must agree with the plain reference.  With
    ``control_bits`` the control's answers, the reference saturated to
    that many bits, stand in for the service's."""
    from bench import reference

    ok = win.ok()
    failed = int((~ok).sum())
    idx = np.flatnonzero(ok)
    k = min(len(idx), int(cfg["check_sample"]))
    if k:
        cells = np.array([len(win.pairs[i][0]) * len(win.pairs[i][1])
                          for i in idx])
        largest = idx[int(cells.argmax())]
        rest = np.setdiff1d(idx, [largest])
        rng = np.random.default_rng(seed)
        pick = [largest] + list(rng.choice(rest, size=k - 1, replace=False))
        pairs = [win.pairs[i] for i in pick]
        local = cfg["kernel"].startswith("local")
        if control_bits is None:
            answers = [win.futures[i].req.result for i in pick]
        else:
            answers = reference.control_answers(pairs, cfg["scoring"], local,
                                                bits=control_bits)
        counts = reference.judge(pairs, answers, cfg["scoring"], local)
    else:
        counts = {"score_or_end_wrong": 0, "path_wrong": 0}
    numbers = {"unanswered": (failed, 0),
               "score_or_end_wrong": (counts["score_or_end_wrong"], 0),
               "path_wrong": (counts["path_wrong"], 0)}
    correct = k > 0 and all(v <= lim for v, lim in numbers.values())
    return {"correct": correct, "failed": failed, "numbers": numbers,
            "compared": k}


def end_to_end(name: str, win: Window, setup_s: float) -> Optional[float]:
    """One end-to-end metric from the client's clock: ``setup_s``,
    ``req_per_s`` (answered inside the window, over the window) or
    ``p<q>_ms`` (the q-th percentile latency of every request due in the
    window, from when it was due; an unanswered one counts as
    infinite)."""
    ok = win.ok()
    if name == "setup_s":
        return setup_s
    if name == "req_per_s":
        return float((ok & (win.done <= win.t1)).sum()) / (win.t1 - win.t0)
    if name.startswith("p") and name.endswith("_ms"):
        lat = np.where(ok, win.done - win.due, np.inf)
        v = float(np.percentile(lat, float(name[1:-3])))
        return v * 1e3 if math.isfinite(v) else None
    raise KeyError(f"no end-to-end metric {name!r}")


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    cell: str
    kernel: str
    window: Window                    # the traced slice of the window
    spans: List                       # repro.obs spans inside it
    trace: Optional[dict]             # devtrace.reduce() of the window
    device_kind: str
    block_for: Callable               # (kernel, bucket) -> rows


def _trace_window(svc, cfg, tr, mx, seconds, backlog, trace_dir):
    """The window with ``repro.obs`` spans on and the profiler running
    over ``TRACE_S`` in its middle; returns the window, the traced slice
    of it, the spans inside the slice and the reduced device trace."""
    import jax
    from bench import devtrace
    from repro.obs import trace as obs_trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    mark = {}
    traced_s = min(seconds, TRACE_S)
    lead_s = (seconds - traced_s) / 2

    def start():
        obs_trace.clear()
        obs_trace.enable(capacity=1 << 20)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        mark["mono"] = time.monotonic()
        mark["ann"] = jax.profiler.TraceAnnotation(devtrace.WINDOW)
        mark["ann"].__enter__()

    def stop():
        if "ann" in mark:
            mark["end"] = time.monotonic()
            mark.pop("ann").__exit__(None, None, None)
        if "mono" in mark and "stopped" not in mark:
            mark["stopped"] = True
            obs_trace.disable()
            jax.profiler.stop_trace()

    try:
        win = run_window(svc, cfg["kernel"], tr, mx, seconds, backlog,
                         at=[(lead_s, start), (lead_s + traced_s, stop)])
    finally:
        stop()
        obs_trace.disable()
    # the per-layer readers see the traced slice of the window
    traced = dataclasses.replace(win, t0=mark["mono"], t1=mark["end"])
    spans = [s for s in obs_trace.spans()
             if s.t1 is not None and traced.t0 <= s.t0 and s.t1 <= traced.t1]
    dropped = obs_trace.dropped()
    pd = devtrace.load(devtrace.find_xplane(trace_dir))
    ann = devtrace.host_annotation(pd, devtrace.WINDOW)
    if ann is None:
        raise RuntimeError("the window annotation is not in the trace")
    offset = ann[0] - mark["mono"]
    lo, hi = traced.t0 + offset, min(traced.t1 + offset, ann[1])
    red = devtrace.reduce(devtrace.tpu_devices(pd), lo, hi,
                          devtrace.host_timeline(obs_trace.spans(), offset))
    red["spans_dropped"] = dropped
    return win, traced, spans, red


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, repo: Path = REPO,
             root: Path = BENCH, out=sys.stdout, err=sys.stderr,
             control_bits: Optional[int] = None) -> dict:
    """One run of ``cell``; prints info lines, the check on stderr and
    the result line last on ``out``, and returns the result."""
    man = manifest(repo)
    w = workload(man, cell)
    devs = device_check(int(w["chips"]), require_tpu)

    import jax
    from repro.runtime import compile_cache

    compile_cache.enable()
    # every program the cell compiles is kept, so the next run loads all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles: List[float] = []

    def on_compile(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        return _run(cell, seed, seconds, trace, w, devs, man, compiles,
                    t_start=t_start, root=root, out=out, err=err,
                    control_bits=control_bits)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)


def _run(cell, seed, seconds, trace, w, devs, man, compiles, *, t_start,
         root, out, err, control_bits) -> dict:
    from repro.runtime import plan as plan_mod
    from bench import traffic

    cfg, mx = config(w["config"], root), mix(w["traffic"], root)
    e2e, layer = cell_metrics(man, cell)
    tr = traffic.generate(mx, seed, traffic.request_count(mx, seconds))
    svc = build_service(cfg)
    warmed = warm(svc, cfg["kernel"], list(zip(tr.queries, tr.refs)))
    backlog = int(mx.get("backlog", 0))
    gc.collect()
    gc.freeze()
    n_compiles = len(compiles)
    plan_compiles = plan_mod.plan_cache_info()["totals"]["compiled"]
    setup_s = time.monotonic() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            win, traced, spans, red = _trace_window(svc, cfg, tr, mx, seconds,
                                                    backlog, trace_dir)
        else:
            win = run_window(svc, cfg["kernel"], tr, mx, seconds, backlog)
            traced, spans, red = win, [], None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    in_window_compiles = len(compiles) - n_compiles
    in_window_plans = (plan_mod.plan_cache_info()["totals"]["compiled"]
                       - plan_compiles)
    stats = dict(svc.stats)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    ctx = Context(cell=cell, kernel=cfg["kernel"], window=traced, spans=spans,
                  trace=red, device_kind=devs[0].device_kind,
                  block_for=svc.block_for)
    gc.unfreeze()

    in_win = win.done <= win.t1
    info = {"cell": cell, "seed": seed, "compiles_in_window":
            in_window_compiles, "plan_compiles_in_window": in_window_plans,
            "submitted": len(win.futures),
            "completed_in_window": int((win.ok() & in_win).sum()),
            "completed_share": float((win.ok() & in_win).sum())
            / max(1, int((win.due <= win.t1).sum())),
            "generator_late_ms": {
                "p50": float(np.percentile(win.lateness_s, 50)) * 1e3,
                "p99": float(np.percentile(win.lateness_s, 99)) * 1e3,
                "max": float(win.lateness_s.max()) * 1e3},
            "dispatcher_errors": win.errors[:5],
            "retries": stats["retries"],
            "dead_lettered": stats["dead_lettered"],
            "warm": warmed}
    if red is not None:
        info["trace"] = {k: v for k, v in red.items() if k != "breakdown"}
    print(json.dumps({"info": info}), file=out, flush=True)

    t_check = time.monotonic()
    verdict = check(win, cfg, seed, control_bits)
    print(json.dumps({"compared": verdict["compared"],
                      "check_s": time.monotonic() - t_check}),
          file=out, flush=True)
    metrics = {}
    if trace:
        for m in layer:
            v = reader(m["name"], root)(ctx)
            if isinstance(v, dict):          # a value with notes beside it
                metrics[m["name"]] = dict(v, unit=m["unit"])
            elif v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e:
            v = end_to_end(m["name"], win, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": verdict["correct"],
              "attempted": len(win.futures), "failed": verdict["failed"],
              "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, (v, lim) in verdict["numbers"].items()}
    for name, (v, lim) in verdict["numbers"].items():
        print(f"check {name} {v} limit {lim}", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
