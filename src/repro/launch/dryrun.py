import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init).  Tests may shrink the fake fleet via env var:
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this prints/records ``compiled.memory_analysis()`` (proves
the footprint) and cost terms (XLA's cost_analysis for reference plus the
while-aware parser in hlo_cost, which the roofline consumes).  A failure
here — sharding mismatch, OOM at compile, unsupported collective — is a
bug in the framework, not in the harness.

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out results.jsonl
"""
import argparse
import gc
import json
import time
import traceback

import jax

from repro import configs
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             mesh=None, verbose: bool = True, save_hlo: str = None,
             rules_version: str = "v1") -> dict:
    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    ok, reason = configs.cell_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "rules": rules_version,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    try:
        cell = build_cell(cfg, shape, mesh, rules_version=rules_version)
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = dict(compiled.cost_analysis() or {})
        hlo_text = compiled.as_text()
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo_text)
        cost = hlo_cost.analyze(hlo_text, n_devices=n_dev)
        rec.update(
            status="ok", lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory={
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_per_device": int(ma.argument_size_in_bytes
                                       + ma.output_size_in_bytes
                                       + ma.temp_size_in_bytes
                                       - ma.alias_size_in_bytes),
            },
            xla_cost={"flops": float(ca.get("flops", -1)),
                      "bytes": float(ca.get("bytes accessed", -1))},
            hlo_cost={"flops_per_device": cost.flops,
                      "bytes_per_device": cost.bytes,
                      "collectives": [
                          {"op": o, "payload_bytes": b, "group": g,
                           "trips": t} for (o, b, g, t) in cost.collectives]},
        )
        if verbose:
            print(f"[{rec['arch']}:{rec['shape']}:{rec['mesh']}] OK "
                  f"compile={t_compile:.1f}s "
                  f"peak/dev={rec['memory']['peak_per_device']/2**30:.2f}GiB "
                  f"flops/dev={cost.flops:.3e} "
                  f"coll_bytes/dev={cost.collective_bytes:.3e}", flush=True)
    except Exception as e:  # noqa: BLE001 — record and continue the matrix
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{rec['arch']}:{rec['shape']}:{rec['mesh']}] FAILED: "
                  f"{type(e).__name__}: {e}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--save-hlo")
    ap.add_argument("--rules", default="v1", choices=["v1", "v2"])
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in configs.ARCH_NAMES:
            for shape in configs.SHAPES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    out_f = open(args.out, "a") if args.out else None
    n_ok = n_skip = n_err = 0
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        for arch, shape in cells:
            rec = run_cell(arch, shape, multi_pod, mesh=mesh,
                           save_hlo=args.save_hlo,
                           rules_version=args.rules)
            n_ok += rec["status"] == "ok"
            n_skip += rec["status"] == "skipped"
            n_err += rec["status"] == "error"
            if out_f:
                out_f.write(json.dumps(rec) + "\n")
                out_f.flush()
            gc.collect()
    print(f"dry-run done: {n_ok} ok, {n_skip} skipped (per assignment), "
          f"{n_err} errors", flush=True)
    if out_f:
        out_f.close()
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
