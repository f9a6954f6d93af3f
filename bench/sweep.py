"""Find the highest rate an open-loop cell sustains, by a sweep on the chip.

    python3 bench/sweep.py --workload short_illumina.poisson \\
        --rates 2000,4000,8000 --seconds 10 --seed 5

One process: the cell's service is built and warmed once, then each rate
gets a window of its own with the cell's mix at that rate.  A rate is
sustained when at least 99% of the requests due in the window were
answered inside it and the backlog did not grow: the median latency of
the last third of the window is under twice that of the first third.
Prints one JSON line per rate, then the highest sustained rate and four
fifths of it, the rate to write into the mix file.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def sustained(row: dict) -> bool:
    return (row["answered_share"] >= 0.99
            and row["p50_last_third_ms"] < 2 * row["p50_first_third_ms"])


def measure(win) -> dict:
    import numpy as np
    ok = win.ok()
    lat = np.where(ok, win.done - win.due, np.inf)
    rel = win.due - win.t0
    span = win.t1 - win.t0
    first = lat[rel < span / 3]
    last = lat[rel >= 2 * span / 3]
    in_time = ok & (win.done <= win.t1)
    return {"offered": len(lat),
            "answered_share": float(in_time.sum()) / max(1, len(lat)),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "p50_first_third_ms": float(np.percentile(first, 50)) * 1e3,
            "p50_last_third_ms": float(np.percentile(last, 50)) * 1e3,
            "generator_late_p99_ms":
                float(np.percentile(win.lateness_s, 99)) * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench import harness, traffic
    from repro.runtime import compile_cache

    w = harness.workload(harness.manifest(), args.workload)
    try:
        harness.device_check(int(w["chips"]), True)
    except harness.NoAccelerator as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 1
    compile_cache.enable()
    cfg, base = harness.config(w["config"]), harness.mix(w["traffic"])
    if base["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    rates = [float(r) for r in args.rates.split(",")]
    mixes = [dict(base, rate_per_s=r) for r in rates]
    trs = [traffic.generate(m, args.seed, traffic.request_count(
        m, args.seconds)) for m in mixes]
    svc = harness.build_service(cfg)
    harness.warm(svc, cfg["kernel"], [p for tr in trs
                                      for p in zip(tr.queries, tr.refs)])
    best = None
    for rate, mx, tr in zip(rates, mixes, trs):
        win = harness.run_window(svc, cfg["kernel"], tr, mx, args.seconds, 0)
        row = dict(rate_per_s=rate, **measure(win))
        row["sustained"] = sustained(row)
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            best = rate if best is None else max(best, rate)
    print(json.dumps({"highest_sustained_per_s": best,
                      "cell_rate_per_s": None if best is None
                      else 0.8 * best}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
