#!/usr/bin/env python
"""Postmortem report over a gateway Chrome trace (Gateway.dump_trace).

Reads a trace-event JSON file, validates it against the schema, and
prints the attribution a chaos-run postmortem needs without opening
Perfetto: per-stage time breakdown (where did the cycles go), per-track
busy time with launch+harvest coverage (is the dispatcher burning host
time off the books), instant-event tallies (retries, dead letters,
kills, respawns), and the top-N slowest spans.

Invariants are checked and any violation makes the exit code nonzero:

* the file must validate against the trace-event schema;
* every worker track's launch+harvest spans must cover >= --min-coverage
  (default 0.90) of its gateway busy time — "harvest time unaccounted"
  means the span instrumentation has a hole.  Stub tracks (a worker
  killed at its first dispatch, an idle poller) carry milliseconds of
  formation time and no launches, so the floor only applies to tracks
  with at least 5% of the busiest worker's gateway time;
* complete events must not overlap on one track (spans on a single
  thread are sequential by construction; overlap means clock misuse).

Examples:
    python scripts/obs_report.py gateway_trace.json
    python scripts/obs_report.py trace.json --top 20 --json report.json
"""
import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.obs import export as obs_export  # noqa: E402

# instants that mark gateway lifecycle events, tallied separately
EVENT_NAMES = ("gw.retry", "gw.dead_letter", "gw.kill", "gw.respawn",
               "gw.degrade")


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def analyze(obj: dict, top: int = 10, min_coverage: float = 0.90) -> dict:
    violations = list(obs_export.validate_chrome_trace(obj))
    events = obj.get("traceEvents", []) if isinstance(obj, dict) else []

    track_names = {}
    for ev in events:
        if isinstance(ev, dict) and ev.get("ph") == "M" \
                and ev.get("name") == "thread_name":
            track_names[ev.get("tid")] = ev["args"]["name"]

    spans = [ev for ev in events if isinstance(ev, dict)
             and ev.get("ph") == "X"
             and isinstance(ev.get("dur"), (int, float))]
    instants = [ev for ev in events if isinstance(ev, dict)
                and ev.get("ph") == "i"]

    # -- per-stage breakdown ------------------------------------------------
    by_stage: dict = collections.defaultdict(
        lambda: {"count": 0, "total_us": 0.0, "max_us": 0.0})
    for ev in spans:
        st = by_stage[ev["name"]]
        st["count"] += 1
        st["total_us"] += ev["dur"]
        st["max_us"] = max(st["max_us"], ev["dur"])
    total_us = sum(st["total_us"] for st in by_stage.values())
    for st in by_stage.values():
        st["frac"] = st["total_us"] / total_us if total_us else 0.0

    # -- per-track busy + coverage + overlap --------------------------------
    tracks: dict = {}
    for ev in spans:
        name = track_names.get(ev.get("tid"), f"tid{ev.get('tid')}")
        t = tracks.setdefault(name, {"busy_us": 0.0, "covered_us": 0.0,
                                     "spans": []})
        if ev.get("cat") == "gateway":
            t["busy_us"] += ev["dur"]
            if ev["name"] in ("gw.launch", "gw.harvest"):
                t["covered_us"] += ev["dur"]
        t["spans"].append((ev["ts"], ev["ts"] + ev["dur"], ev["name"]))
    max_busy = max((t["busy_us"] for n, t in tracks.items()
                    if n.startswith("gw-")), default=0.0)
    for name, t in tracks.items():
        t["coverage"] = (t["covered_us"] / t["busy_us"]
                         if t["busy_us"] else None)
        spans_sorted = sorted(t.pop("spans"))
        # nested child spans (dispatch.* around gw.*) are legitimate;
        # only *partial* overlap between siblings is a clock violation
        stack = []
        for s0, s1, nm in spans_sorted:
            while stack and stack[-1][1] <= s0:
                stack.pop()
            if stack and s1 > stack[-1][1]:
                violations.append(
                    f"track {name}: span {nm!r} at {s0:.0f}us partially "
                    f"overlaps {stack[-1][2]!r} (monotonic-clock misuse)")
                break
            stack.append((s0, s1, nm))
        t["stub"] = t["busy_us"] < 0.05 * max_busy
        if name.startswith("gw-") and not t["stub"] \
                and t["coverage"] is not None \
                and t["coverage"] < min_coverage:
            violations.append(
                f"track {name}: launch+harvest cover only "
                f"{t['coverage']:.1%} of gateway busy time "
                f"(floor {min_coverage:.0%}) — harvest time unaccounted")

    # -- instant-event tallies ----------------------------------------------
    event_counts = collections.Counter(
        ev["name"] for ev in instants if ev.get("name") in EVENT_NAMES)

    slowest = sorted(spans, key=lambda ev: -ev["dur"])[:top]
    return {
        "n_events": len(events),
        "n_spans": len(spans),
        "total_span_us": total_us,
        "stages": {k: dict(v) for k, v in sorted(
            by_stage.items(), key=lambda kv: -kv[1]["total_us"])},
        "tracks": tracks,
        "events": dict(event_counts),
        "slowest": [{"name": ev["name"], "dur_us": ev["dur"],
                     "ts_us": ev["ts"],
                     "track": track_names.get(ev.get("tid"),
                                              f"tid{ev.get('tid')}"),
                     "args": ev.get("args", {})} for ev in slowest],
        "violations": violations,
    }


def print_report(rep: dict) -> None:
    print(f"trace: {rep['n_events']} events, {rep['n_spans']} spans, "
          f"{rep['total_span_us'] / 1e3:.1f} ms total span time")
    print("\nper-stage breakdown:")
    print(f"  {'stage':<22}{'count':>7}{'total ms':>11}"
          f"{'max ms':>9}{'share':>8}")
    for name, st in rep["stages"].items():
        print(f"  {name:<22}{st['count']:>7}"
              f"{st['total_us'] / 1e3:>11.2f}"
              f"{st['max_us'] / 1e3:>9.2f}{st['frac']:>8.1%}")
    print("\nper-track busy time:")
    for name, t in sorted(rep["tracks"].items()):
        cov = ("n/a" if t["coverage"] is None
               else f"{t['coverage']:.1%}")
        tag = "  (stub: not gated)" if t.get("stub") else ""
        print(f"  {name:<22}busy={t['busy_us'] / 1e3:>9.2f} ms  "
              f"launch+harvest coverage={cov}{tag}")
    if rep["events"]:
        print("\nlifecycle events: " + ", ".join(
            f"{k}={v}" for k, v in sorted(rep["events"].items())))
    print(f"\ntop {len(rep['slowest'])} slowest spans:")
    for s in rep["slowest"]:
        print(f"  {s['dur_us'] / 1e3:>9.2f} ms  {s['name']:<18} "
              f"on {s['track']}  args={s['args']}")
    for v in rep["violations"]:
        print(f"VIOLATION: {v}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("trace", help="Chrome trace-event JSON "
                                  "(Gateway.dump_trace output)")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest spans to list (default 10)")
    ap.add_argument("--min-coverage", type=float, default=0.90,
                    help="launch+harvest floor on worker tracks "
                         "(default 0.90)")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write the report as JSON to OUT")
    args = ap.parse_args(argv)

    rep = analyze(load(args.trace), top=args.top,
                  min_coverage=args.min_coverage)
    print_report(rep)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    if rep["violations"]:
        print(f"obs report: {len(rep['violations'])} invariant "
              f"violation(s)")
        return 1
    return 0


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    sys.exit(main())
