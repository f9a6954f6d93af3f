#!/usr/bin/env python
"""Seeded chaos sweep over the serving gateway.

For each seed, a mixed-length alignment workload drains twice: once
fault-free (inline oracle) and once through a multi-worker ``serve()``
pool under a :class:`~repro.serve.FaultPlan` that kills the first
``--kill`` workers at their second dispatch and fails launches/harvests
with the seeded probabilities.  The sweep then asserts the gateway's
fault-tolerance invariants:

* every submitted request resolves — with a result bit-identical to the
  fault-free run, or a *typed* dead-letter error after bounded retries;
* zero double-completions (completed + dead-lettered == submitted);
* the kill schedule fired and stranded batches were redispatched.

Any violation is reported and the exit code is nonzero — this is the
scriptable face of the ``bench_faults`` chaos gate, cheap enough for
tier-1 (see scripts/tier1.sh) and sweepable over many seeds locally.

Examples:
    python scripts/chaos.py                       # 3-seed default sweep
    python scripts/chaos.py --seeds 0 7 42 --requests 128 --workers 6
    python scripts/chaos.py --fail-launch-p 0.3 --max-retries 2  # letters
    python scripts/chaos.py --json chaos_report.json
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

KNOWN_KINDS = {"deadline", "retries", "shed", "injected", "killed",
               "timeout", "error"}


def build_stream(np, AlignRequest, seed, n, lo, hi):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        lq = min(hi, lo + int(rng.exponential(scale=(hi - lo) / 3.0)))
        lr = min(hi, lo + int(rng.exponential(scale=(hi - lo) / 3.0)))
        reqs.append(AlignRequest(
            rid=i, kernel="global_affine",
            query=rng.integers(0, 4, lq).astype(np.uint8),
            ref=rng.integers(0, 4, lr).astype(np.uint8)))
    return reqs


def run_seed(seed, args):
    import numpy as np

    from repro.serve import (AlignRequest, AlignmentService, FaultPlan,
                             GatewayTimeout)

    violations = []

    def service(**kw):
        return AlignmentService(
            max_len=args.max_len, block=args.block, coalesce=False,
            pipeline_depth=2, **kw)

    base = build_stream(np, AlignRequest, seed, args.requests, 24,
                        args.max_len)

    def clone():
        return [AlignRequest(rid=r.rid, kernel=r.kernel, query=r.query,
                             ref=r.ref) for r in base]

    oracle = service()
    ref = clone()
    oracle.submit_all(ref)
    oracle.drain()

    plan = FaultPlan(
        seed=seed,
        kill={f"w{i}": 1 for i in range(args.kill)},
        fail_launch_p=args.fail_launch_p,
        fail_harvest_p=args.fail_harvest_p,
        latency_s=args.latency_s, latency_p=args.latency_p)
    svc = service(fault_plan=plan, redispatch_after=0.75,
                  max_retries=args.max_retries)
    reqs = clone()
    svc.submit_all(reqs)
    t0 = time.perf_counter()
    try:
        stats = svc.serve(n_workers=args.workers, timeout_s=args.timeout_s)
    except GatewayTimeout as exc:
        violations.append(f"serve() timed out: {exc}")
        stats = dict(svc.stats)
    wall_s = time.perf_counter() - t0

    dead_rids = {d["rid"] for d in svc.dead_letters}
    completed = mismatched = lettered = 0
    for r, want in zip(reqs, ref):
        if r.result is None:
            violations.append(f"rid {r.rid}: never resolved")
        elif r.result.get("failed"):
            lettered += 1
            kind = r.result["error"].get("kind")
            if kind not in KNOWN_KINDS:
                violations.append(f"rid {r.rid}: untyped failure {kind!r}")
            if r.rid not in dead_rids:
                violations.append(
                    f"rid {r.rid}: failed result without a dead-letter "
                    f"record")
        else:
            completed += 1
            if r.result != want.result:
                mismatched += 1
    if mismatched:
        violations.append(
            f"{mismatched} completed results diverge from the fault-free "
            f"run (recovery must never change answers)")
    if stats["completed"] + lettered != args.requests:
        violations.append(
            f"completed {stats['completed']} + dead-lettered {lettered} "
            f"!= {args.requests} submitted (lost or double-counted work)")
    killed = sorted(k["worker"] for k in stats["killed"])
    if args.kill and killed != [f"w{i}" for i in range(args.kill)]:
        violations.append(f"kill schedule misfired: killed={killed}")
    if args.kill and stats["redispatched"] < 1:
        violations.append("no stranded batch was ever redispatched")

    # the metrics snapshot must reconcile exactly with what this script
    # counted off the futures — same invariant, independent bookkeeping
    m = svc.metrics()
    rec = m["reconcile"]
    if not rec["ok"]:
        violations.append(f"metrics snapshot does not reconcile: {rec}")
    if rec["dead_lettered"] != lettered:
        violations.append(
            f"metrics count {rec['dead_lettered']} dead letters; "
            f"futures show {lettered}")
    counters = m["metrics"]["counters"]
    for kind, k_n in m["dead_letters_by_kind"].items():
        got = int(counters.get(f"gw_dead_letters_total{{kind={kind}}}", 0))
        if got != k_n:
            violations.append(
                f"dead-letter metric kind={kind}: {got} != {k_n} records")
    if int(counters.get("gw_retries_total", 0)) != int(stats["retries"]):
        violations.append(
            f"retry metric {counters.get('gw_retries_total')} != "
            f"stats {stats['retries']}")

    return {
        "seed": seed, "wall_s": round(wall_s, 3),
        "completed": completed, "dead_lettered": lettered,
        "identical": mismatched == 0,
        "killed": killed,
        "redispatched": int(stats["redispatched"]),
        "retries": int(stats["retries"]),
        "faults": int(stats["faults"]),
        "dead_letters": [dict(d) for d in svc.dead_letters],
        "dead_letters_by_kind": m["dead_letters_by_kind"],
        "reconcile": rec,
        "violations": violations,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seeds", nargs="+", type=int, default=[0, 1, 2],
                    help="fault-plan + workload seeds (default: 0 1 2)")
    ap.add_argument("--requests", type=int, default=48,
                    help="requests per seed (default 48)")
    ap.add_argument("--workers", type=int, default=4,
                    help="dispatcher pool size (default 4)")
    ap.add_argument("--kill", type=int, default=2,
                    help="workers killed at their 2nd dispatch (default 2)")
    ap.add_argument("--fail-launch-p", type=float, default=0.1)
    ap.add_argument("--fail-harvest-p", type=float, default=0.05)
    ap.add_argument("--latency-s", type=float, default=0.0)
    ap.add_argument("--latency-p", type=float, default=0.0)
    ap.add_argument("--max-retries", type=int, default=6)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--block", type=int, default=2,
                    help="batch rows per dispatch (small = many batches)")
    ap.add_argument("--timeout-s", type=float, default=300.0)
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write the sweep report to OUT")
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="record spans for the faulty runs and write a "
                         "Perfetto-loadable Chrome trace to OUT")
    args = ap.parse_args(argv)
    if args.kill > args.workers:
        ap.error(f"--kill {args.kill} > --workers {args.workers}")

    if args.trace:
        from repro.obs import trace as obs_trace
        obs_trace.enable()

    reports = []
    for seed in args.seeds:
        rep = run_seed(seed, args)
        reports.append(rep)
        status = "ok" if not rep["violations"] else "FAIL"
        print(f"chaos seed={seed}: {status} completed={rep['completed']} "
              f"dead_lettered={rep['dead_lettered']} "
              f"killed={len(rep['killed'])} "
              f"redispatched={rep['redispatched']} "
              f"retries={rep['retries']} wall_s={rep['wall_s']}",
              flush=True)
        shown = rep["dead_letters"][:20]
        for d in shown:
            print(f"  dead-letter rid={d['rid']} kind={d['kind']} "
                  f"worker={d['worker']} attempts={d['attempts']} "
                  f"ts={d['ts']:.3f}", flush=True)
        if len(rep["dead_letters"]) > len(shown):
            print(f"  ... and {len(rep['dead_letters']) - len(shown)} "
                  f"more dead letters", flush=True)
        for v in rep["violations"]:
            print(f"  VIOLATION: {v}", flush=True)

    violations = [v for rep in reports for v in rep["violations"]]
    out = {"config": {k: v for k, v in vars(args).items()
                      if k not in ("json", "trace")},
           "seeds": reports, "ok": not violations}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}", flush=True)
    if args.trace:
        from repro.obs import export as obs_export
        obj = obs_export.write_chrome_trace(args.trace)
        obs_trace.disable()
        errs = obs_export.validate_chrome_trace(obj)
        if errs:
            violations.extend(f"trace: {e}" for e in errs)
        print(f"wrote {args.trace} ({len(obj['traceEvents'])} events, "
              f"{'INVALID' if errs else 'valid'})", flush=True)
    if violations:
        print(f"chaos sweep: {len(violations)} invariant violation(s)",
              flush=True)
        return 1
    print(f"chaos sweep: all invariants held across "
          f"{len(args.seeds)} seed(s)", flush=True)
    return 0


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    sys.exit(main())
