#!/usr/bin/env python
"""Sweep the plan design space and commit the winners to a tuning table.

    PYTHONPATH=src python scripts/autotune.py \
        --kernels global_linear,global_affine --engines wavefront \
        --buckets 64,128,256 --batches 8 --out TUNE_TABLE.json

Each (kernel, engine, bucket, batch) point enumerates the engine's legal
schedule grid, prunes it with the lowered-HLO roofline, compiles and
times the survivors (parity-gated against the hand-picked default), and
records the measured winner.  The written table is consulted by
``runtime.plan.get_plan`` whenever a caller passes no explicit schedule
option; ``REPRO_TUNE_TABLE=off`` disables it.

Entries are keyed by backend and JAX version, so re-running after an
upgrade refreshes rather than poisons: stale entries simply stop
matching.  ``--merge`` starts from an existing table (default when
``--out`` exists) so sweeps can be grown incrementally.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser(
        description="autotune plan schedules into a persisted table")
    ap.add_argument("--kernels", default="global_linear,global_affine",
                    help="comma-separated kernels_zoo names")
    ap.add_argument("--engines", default="wavefront",
                    help="comma-separated engine names")
    ap.add_argument("--buckets", default="64,128,256",
                    help="comma-separated square bucket lengths")
    ap.add_argument("--batches", default="8",
                    help="comma-separated batch sizes ('single' = "
                         "un-batched plan)")
    ap.add_argument("--out", default=None,
                    help="table path (default: repo-root TUNE_TABLE.json)")
    ap.add_argument("--top-k", type=int, default=4,
                    help="candidates the cost model keeps per point")
    ap.add_argument("--iters", type=int, default=3,
                    help="timing repeats per candidate (median)")
    ap.add_argument("--fresh", action="store_true",
                    help="ignore an existing table instead of merging")
    args = ap.parse_args()

    # the sweep must measure against the hand-picked defaults, never an
    # already-installed table
    os.environ["REPRO_TUNE_TABLE"] = "off"

    from repro import tune

    out = args.out or str(tune.default_path())
    table = None
    if not args.fresh and os.path.isfile(out):
        table = tune.TuningTable.load(out)
        print(f"# merging into {out} ({len(table)} entries)")

    def parse_batch(tok: str):
        return None if tok.strip() == "single" else int(tok)

    points = [(k.strip(), e.strip(), (int(b), int(b)), parse_batch(n))
              for k in args.kernels.split(",")
              for e in args.engines.split(",")
              for b in args.buckets.split(",")
              for n in args.batches.split(",")]
    print(f"# sweeping {len(points)} points "
          f"(top_k={args.top_k}, iters={args.iters})")
    table = tune.run_sweep(points, table=table, top_k=args.top_k,
                           iters=args.iters, log=lambda m: print(f"# {m}"))
    table.save(out)
    print(f"# wrote {out} ({len(table)} entries)")

    from repro.runtime import plan as plan_mod
    totals = plan_mod.plan_cache_info()["totals"]
    print(f"# compiled {totals['compiled']} plans, "
          f"{totals['compile_s']:.1f}s total compile time")


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    main()
