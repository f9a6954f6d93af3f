"""Benchmark orchestrator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus section headers on
stderr-free stdout comments).  ``--quick`` shrinks sizes for CI.
``--json out.json`` additionally dumps each suite's headline metrics
(whatever dict its ``run()`` returns) — the perf-trajectory artifact
(e.g. the committed ``BENCH_fill.json`` baseline).

``--compare BENCH_<name>.json`` diffs the fresh run against a committed
baseline: each suite module may declare ``HEADLINES = {dotted.path:
"higher"|"lower"}`` naming the metrics that constitute its perf
contract, and a headline moving >20% the wrong way fails the run
(exit 1).  Non-headline metrics are informational and never gate.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from repro.obs import metrics as obs_metrics
from repro.runtime import plan as plan_mod

from . import (bench_kernels_table2, bench_scaling_fig3,
               bench_vs_handcoded_fig45, bench_vs_software_fig6,
               bench_vs_naive_hls, bench_tiling, bench_bucketing,
               bench_mapping, bench_serving, bench_fill, bench_pairhmm,
               bench_filter, bench_autotune, bench_faults, bench_obs)

SUITES = [
    ("Table 2 (15 kernels)", bench_kernels_table2),
    ("Fig 3 (N_PE / N_B scaling)", bench_scaling_fig3),
    ("Fig 4/5 (vs hand-coded)", bench_vs_handcoded_fig45),
    ("Fig 6 (vs software baseline)", bench_vs_software_fig6),
    ("S7.5 (vs naive-HLS schedule)", bench_vs_naive_hls),
    ("Tiling (claim 5)", bench_tiling),
    ("Bucketed batching (runtime)", bench_bucketing),
    ("Read mapping (seed-and-extend)", bench_mapping),
    ("Serving (sync vs pipelined drain)", bench_serving),
    ("Fill (strip-mined + packed tb)", bench_fill),
    ("Pair-HMM (forward + genotyping)", bench_pairhmm),
    ("Filter ladder (myers vs full DP)", bench_filter),
    ("Autotune (sweep + warm boot)", bench_autotune),
    ("Faults (chaos gate: kill 2 of 4)", bench_faults),
    ("Observability (overhead + trace gates)", bench_obs),
]

# a headline may regress by this fraction before --compare fails
COMPARE_TOLERANCE = 0.20


def _resolve(metrics, dotted: str):
    cur = metrics
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) else None


def compare_metrics(fresh: dict, baseline: dict,
                    tolerance: float = COMPARE_TOLERANCE) -> int:
    """Diff fresh vs baseline headline metrics; returns #regressions.

    Only suites present in *both* dumps are compared, and only the
    dotted paths their module's ``HEADLINES`` declares.  A ``"higher"``
    headline regresses when fresh < baseline * (1 - tolerance); a
    ``"lower"`` one when fresh > baseline * (1 + tolerance).
    """
    by_name = {mod.__name__.rsplit(".", 1)[-1]: mod for _, mod in SUITES}
    regressions = 0
    for modname, base_metrics in sorted(baseline.items()):
        mod = by_name.get(modname)
        headlines = getattr(mod, "HEADLINES", None) if mod else None
        if not headlines or modname not in fresh:
            continue
        for dotted, direction in sorted(headlines.items()):
            b = _resolve(base_metrics, dotted)
            f = _resolve(fresh[modname], dotted)
            if b is None or f is None:
                print(f"# compare {modname}.{dotted}: missing "
                      f"(baseline={b}, fresh={f}) — skipped", flush=True)
                continue
            if direction == "higher":
                bad = f < b * (1 - tolerance)
            else:
                bad = f > b * (1 + tolerance)
            tag = "REGRESSION" if bad else "ok"
            print(f"# compare {modname}.{dotted}: baseline={b:.4g} "
                  f"fresh={f:.4g} ({direction} is better) {tag}",
                  flush=True)
            regressions += bad
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="dump each suite's headline metrics to OUT")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="diff fresh metrics against a committed "
                         "BENCH_<name>.json; exit 1 on >20%% headline "
                         "regression")
    args = ap.parse_args()
    baseline = None
    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
    print("name,us_per_call,derived")
    failures = 0
    metrics: dict = {}
    for title, mod in SUITES:
        if args.only and args.only not in mod.__name__:
            continue
        if baseline is not None and not args.only \
                and mod.__name__.rsplit(".", 1)[-1] not in baseline:
            continue            # compare runs only re-measure the baseline
        print(f"# --- {title} ---", flush=True)
        try:
            out = mod.run(quick=args.quick)
            if isinstance(out, dict):
                # regression attribution without a rerun: every suite's
                # dump carries the process-global metrics (plan-cache
                # hit/miss/compile counters) and cumulative plan totals
                # as they stood when the suite finished — a slow fresh
                # run with a fat compile_s delta is a compile storm, not
                # a slow kernel
                out = dict(
                    out, observability={
                        "metrics": obs_metrics.get_registry().snapshot(),
                        "plan_cache_totals":
                            plan_mod.plan_cache_info()["totals"],
                    })
                metrics[mod.__name__.rsplit(".", 1)[-1]] = out
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(metrics, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)
        # the committed per-suite baselines (BENCH_fill.json etc.) are
        # written here too, so a trajectory refresh is one command and
        # the canonical files can't drift from the combined dump
        # (full mode only — quick metrics are not baselines)
        for modname, out in [] if args.quick else metrics.items():
            short = modname.removeprefix("bench_")
            path = f"BENCH_{short}.json"
            with open(path, "w") as f:
                json.dump({modname: out}, f, indent=2, sort_keys=True)
            print(f"# wrote {path}", flush=True)
    if baseline is not None:
        failures += compare_metrics(metrics, baseline)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    main()
