"""End-to-end smoke run of the served paths on one TPU chip.

    python chip_smoke.py                 # one TPU chip, full sizes
    python chip_smoke.py --chips 4       # the 4-chip data-mesh phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny, CPU, interpret

Phases (one JSON line each, then a final status line):

* ``align`` — 1,024 requests through ``AlignmentService.submit`` on its
  default engine: 896 short-read extensions (``global_affine``) and 128
  long pairs (``local_affine``).  Every future must resolve to an
  alignment, the gateway must reconcile with no dead letter and no retry,
  32 short pairs must match the row-major ``reference`` engine run on the
  host CPU, and every returned short path (and a sample of long ones) must
  rescore to its reported score.
* ``pallas`` — the paper's systolic kernel: 32 short and 8 long pairs
  through the compiled ``pallas`` plan must equal the ``wavefront`` plan
  bit for bit (score, end cell, moves), and ``myers_pallas`` must equal
  ``myers`` on ``edit_distance``.  The lowered programs must hold a
  ``tpu_custom_call``.
* ``map`` — ``ReadMappingService`` with its default myers screen over a
  1 Mb synthetic reference: 1,024 reads at 1% error plus 256 junk reads.
* ``genotype`` — ``GenotypingService`` on 8 pair-HMM sites; calls must
  match the truth and forward likelihoods must match the host reference.

``--chips 4`` runs only ``mesh``: the ``align`` traffic through
``AlignmentService`` over a 4-device data mesh, compared bit for bit with
the same service on one device, and each block's outputs must sit on 4
distinct devices.

Timings are printed as ``first_chip_run`` (compiles included): they are a
record of this run, not a benchmark.  Without a TPU the script prints an
``"ok": false`` line and exits 1; ``--rehearse`` runs tiny sizes on any
platform with the interpret-mode Pallas engines.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
# everything that compiles comes from committed files: no tune table
os.environ["REPRO_TUNE_TABLE"] = "off"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import alphabets, kernels_zoo, rescore  # noqa: E402
from repro.core import types as T  # noqa: E402
from repro.core.batch import make_sharded_aligner  # noqa: E402
from repro.data.synthetic import (junk_reads, sample_reads,  # noqa: E402
                                  sample_site)
from repro.prob import kernels as prob_kernels  # noqa: E402
from repro.runtime import compile_cache, registry  # noqa: E402
from repro.runtime import plan as plan_mod  # noqa: E402
from repro.serve.alignment_service import (  # noqa: E402
    AlignmentService, AlignRequest)
from repro.serve.genotyping_service import (  # noqa: E402
    GenotypeRequest, GenotypingService)
from repro.serve.mapping_service import (  # noqa: E402
    MapRequest, ReadMappingService)

GIB = 1 << 30

# Full sizes (one chip) and rehearsal sizes (CPU, interpret mode).
FULL = dict(n_short=896, n_long=128, short_len=(100, 250), window=300,
            long_len=(1000, 4096), max_len=4096, n_oracle=32,
            n_long_rescore=16, pallas_short_bucket=(256, 512),
            pallas_long=8, pallas_long_bucket=4096, myers_bucket=512,
            myers_pairs=16, ref_len=1 << 20, n_reads=1024, n_junk=256,
            n_sites=8, site_reads=64, hap_len=400, read_len=150,
            n_ll=8, pallas="pallas", myers_pallas="myers_pallas")
REHEARSE = dict(n_short=24, n_long=4, short_len=(40, 90), window=110,
                long_len=(150, 250), max_len=256, n_oracle=8,
                n_long_rescore=4, pallas_short_bucket=(128, 128),
                pallas_long=2, pallas_long_bucket=256, myers_bucket=128,
                myers_pairs=8, ref_len=1 << 15, n_reads=64, n_junk=16,
                n_sites=2, site_reads=8, hap_len=120, read_len=60,
                n_ll=4, pallas="pallas_interpret",
                myers_pallas="myers_pallas_interpret")


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def compile_seconds() -> float:
    return float(plan_mod.plan_cache_info()["totals"]["compile_s"])


# -- traffic -----------------------------------------------------------------
def align_traffic(rng, sz):
    """Short-read extensions (global) then long pairs (local)."""
    reqs = []
    for rid in range(sz["n_short"]):
        n = int(rng.integers(sz["short_len"][0], sz["short_len"][1] + 1))
        window = alphabets.random_dna(rng, int(rng.integers(n, sz["window"] + 1)))
        start = int(rng.integers(0, len(window) - n + 1))
        read = alphabets.mutate(rng, window[start:start + n], 0.05)
        reqs.append(("global_affine", read[:sz["max_len"]], window))
    for _ in range(sz["n_long"]):
        n = int(rng.integers(sz["long_len"][0], sz["long_len"][1] + 1))
        ref = alphabets.random_dna(rng, n)
        qry = alphabets.mutate(rng, ref, 0.10)[:sz["max_len"]]
        reqs.append(("local_affine", qry, ref))
    return reqs


def serve_align(traffic, sz, mesh=None, spread=None):
    """Submit ``traffic`` to an AlignmentService; return (results, metrics).
    With ``spread``, record the devices holding each block's outputs."""
    svc = AlignmentService(max_len=sz["max_len"], tb_budget_bytes=2 * GIB,
                           mesh=mesh)
    if spread is not None:
        for kernel in {k for k, _, _ in traffic}:
            spec, params = kernels_zoo.make(kernel)
            svc.channels[kernel] = (spec, params, _recording(
                make_sharded_aligner(spec, mesh, with_traceback=True),
                spread))
    futs = [svc.submit(AlignRequest(rid=i, kernel=k, query=q, ref=r))
            for i, (k, q, r) in enumerate(traffic)]
    svc.wait(futs)
    return [f.result() for f in futs], svc.metrics()


def _recording(fn, spread):
    def run(*args):
        out = fn(*args)
        spread.append(sorted({s.device.id for s in
                              out.score.addressable_shards}))
        return out
    return run


def reconcile(metrics, n: int) -> dict:
    st, rec = metrics["stats"], metrics["reconcile"]
    check(rec["ok"], f"gateway does not reconcile: {rec}")
    check(st["submitted"] == n and st["completed"] == n,
          f"submitted {st['submitted']} completed {st['completed']} != {n}")
    check(st["dead_lettered"] == 0, f"{st['dead_lettered']} dead letters")
    check(st["retries"] == 0, f"{st['retries']} retries")
    return {"dead_letters": st["dead_lettered"], "retries": st["retries"]}


def alignment_from_result(res) -> T.Alignment:
    """Rebuild the move string of a served result from its CIGAR
    (``M`` diag, ``D`` up, ``I`` left; start -> end order)."""
    code = {"M": T.MOVE_DIAG, "D": T.MOVE_UP, "I": T.MOVE_LEFT}
    moves = []
    for n, op in re.findall(r"(\d+)([MDI])", res["cigar"]):
        moves += [code[op]] * int(n)
    di = sum(m in (T.MOVE_DIAG, T.MOVE_UP) for m in moves)
    dj = sum(m in (T.MOVE_DIAG, T.MOVE_LEFT) for m in moves)
    end_i, end_j = res["end"]
    return T.Alignment(score=res["score"], end_i=end_i, end_j=end_j,
                       start_i=end_i - di, start_j=end_j - dj,
                       moves=np.asarray(moves[::-1], np.uint8),
                       n_moves=len(moves))


def cpu_reference(spec, params, pairs):
    """(score, end_i, end_j) of each pair from the row-major oracle, run
    on the host's CPU device."""
    cpu = jax.devices("cpu")[0]
    ref = registry.get_engine("reference")
    fill = jax.jit(lambda p, q, r: ref(spec, p, q, r))
    out = []
    for q, r in pairs:
        p, qd, rd = jax.device_put((params, jnp.asarray(q), jnp.asarray(r)),
                                   cpu)
        res = fill(p, qd, rd)
        out.append((float(res.score), int(res.end_i), int(res.end_j)))
    return out


# -- phases ------------------------------------------------------------------
def phase_align(traffic, sz) -> dict:
    results, metrics = serve_align(traffic, sz)
    n = len(traffic)
    for (k, _, _), res in zip(traffic, results):
        check("cigar" in res and "failed" not in res and
              not res.get("filtered") and not res.get("degraded"),
              f"not an alignment: {res}")
    counts = reconcile(metrics, n)

    spec, params = kernels_zoo.make("global_affine")
    n_short = sz["n_short"]
    oracle = cpu_reference(spec, params,
                           [(q, r) for _, q, r in traffic[:sz["n_oracle"]]])
    for i, (score, ei, ej) in enumerate(oracle):
        res = results[i]
        check(res["score"] == score and tuple(res["end"]) == (ei, ej),
              f"pair {i}: served {res['score']} {res['end']} != reference "
              f"{score} ({ei}, {ej})")
    lspec, lparams = kernels_zoo.make("local_affine")
    long_ids = range(n_short, n_short + min(sz["n_long_rescore"],
                                            sz["n_long"]))
    for ids, sp, pr in ((range(n_short), spec, params),
                        (long_ids, lspec, lparams)):
        for i in ids:
            _, q, r = traffic[i]
            got = rescore.rescore(sp, pr, q, r,
                                  alignment_from_result(results[i]))
            check(got == results[i]["score"],
                  f"pair {i}: path rescores to {got}, reported "
                  f"{results[i]['score']}")
    return dict(requests=n, **counts, oracle_pairs=len(oracle),
                rescored=n_short + len(long_ids))


def _padded(pairs, bucket):
    qb, rb = bucket
    qs = np.zeros((len(pairs), qb), np.uint8)
    rs = np.zeros((len(pairs), rb), np.uint8)
    ql = np.zeros((len(pairs),), np.int32)
    rl = np.zeros((len(pairs),), np.int32)
    for i, (q, r) in enumerate(pairs):
        qs[i, :len(q)], rs[i, :len(r)] = q, r
        ql[i], rl[i] = len(q), len(r)
    return [jnp.asarray(a) for a in (qs, rs, ql, rl)]


def _run_plans(spec, params, engines, pairs, bucket, **kw):
    """Run ``pairs`` through each engine's plan; return host outputs and
    whether each engine's lowered program holds a TPU kernel."""
    args = _padded(pairs, bucket)
    outs, kernels = [], []
    for eng in engines:
        plan = plan_mod.get_plan(spec, eng, (bucket[0],), (bucket[1],),
                                 batch_size=len(pairs), **kw)
        outs.append(jax.tree.map(np.asarray, plan(params, *args)))
        hlo = plan_mod.lower_plan_hlo(spec, params, eng, (bucket[0],),
                                      (bucket[1],), batch_size=len(pairs),
                                      **kw)
        kernels.append("tpu_custom_call" in hlo)
    return outs, kernels


def _same(a, b, fields, what):
    for f in fields:
        x, y = getattr(a, f), getattr(b, f)
        check(np.array_equal(x, y), f"{what}: {f} differs")


def phase_pallas(rng, traffic, sz, on_tpu: bool) -> dict:
    short = [(q, r) for _, q, r in traffic[:sz["n_oracle"]]]
    spec, params = kernels_zoo.make("global_affine")
    fields = ("score", "end_i", "end_j", "start_i", "start_j", "moves",
              "n_moves")
    kernels = []
    (pl_out, wf_out), kern = _run_plans(
        spec, params, [sz["pallas"], "wavefront"], short,
        sz["pallas_short_bucket"])
    _same(pl_out, wf_out, fields, "short pairs")
    kernels.append(kern[0])
    long_pairs = []
    for _ in range(sz["pallas_long"]):
        n = int(rng.integers(sz["long_len"][0], sz["long_len"][1] + 1))
        ref = alphabets.random_dna(rng, n)
        long_pairs.append((alphabets.mutate(rng, ref, 0.10)[:sz["max_len"]],
                           ref))
    b = sz["pallas_long_bucket"]
    (pl_out, wf_out), kern = _run_plans(
        spec, params, [sz["pallas"], "wavefront"], long_pairs, (b, b))
    _same(pl_out, wf_out, fields, "long pairs")
    kernels.append(kern[0])

    espec, eparams = kernels_zoo.make("edit_distance")
    mb = sz["myers_bucket"]
    edit = [(q[:mb], r[:mb]) for _, q, r in traffic[:sz["myers_pairs"]]]
    (my_pl, my_x), kern = _run_plans(
        espec, eparams, [sz["myers_pallas"], "myers"], edit, (mb, mb),
        with_traceback=False)
    _same(my_pl, my_x, ("score", "end_i", "end_j"), "myers")
    kernels.append(kern[0])
    if on_tpu:
        check(all(kernels), f"no tpu_custom_call in lowered plans: {kernels}")
    return dict(requests=len(short) + len(long_pairs) + len(edit),
                dead_letters=0, retries=0,
                pallas_pairs=len(short) + len(long_pairs),
                myers_pairs=len(edit), tpu_custom_call=all(kernels))


def phase_map(rng, sz, seed: int) -> dict:
    ref = alphabets.random_dna(rng, sz["ref_len"])
    reads = sample_reads(ref, sz["n_reads"], 150, error_rate=0.01,
                         seed=seed + 1)
    read_list = [reads.reads[i, :reads.lens[i]] for i in range(sz["n_reads"])]
    read_list += junk_reads(rng, ref, sz["n_junk"], 150)
    svc = ReadMappingService(ref)
    reqs = [MapRequest(rid=i, read=r) for i, r in enumerate(read_list)]
    for r in reqs:
        svc.submit(r)
    svc.wait()
    counts = reconcile(svc.metrics(), len(reqs))
    n = sz["n_reads"]
    # mapped within 5 bp of the true origin
    hits = sum(1 for i in range(n) if reqs[i].result["mapped"] and
               abs((reqs[i].result["pos"] - 1) - int(reads.pos[i])) <= 5)
    junk_rejected = sum(not r.result["mapped"] for r in reqs[n:])
    accuracy = hits / n
    check(accuracy >= 0.95, f"mapping accuracy {accuracy} < 0.95")
    return dict(requests=len(reqs), **counts, ref_bp=sz["ref_len"],
                accuracy=accuracy,
                junk_rejected_share=junk_rejected / max(sz["n_junk"], 1))


def phase_genotype(rng, sz, seed: int) -> dict:
    truths = [(0, 0), (0, 1), (1, 1)]
    sites = [sample_site(seed=seed * 100 + s, hap_len=sz["hap_len"],
                         read_len=sz["read_len"], n_reads=sz["site_reads"],
                         genotype=truths[s % 3])
             for s in range(sz["n_sites"])]
    svc = GenotypingService()
    futs = [svc.submit(GenotypeRequest(rid=i, reads=s.reads,
                                       haplotypes=s.haplotypes))
            for i, s in enumerate(sites)]
    svc.wait(futs)
    results = [f.result() for f in futs]
    counts = reconcile(svc.metrics(), len(sites))
    for i, (s, res) in enumerate(zip(sites, results)):
        check(tuple(sorted(res["GT"])) == tuple(sorted(s.genotype)),
              f"site {i}: called {res['GT']}, truth {s.genotype}")
    spec = prob_kernels.cached_pairhmm()
    params = prob_kernels.default_params()
    cells = [(i % len(sites), i % sz["site_reads"], i % 2)
             for i in range(sz["n_ll"])]
    oracle = cpu_reference(spec, params, [
        (sites[s].reads[ri], sites[s].haplotypes[hi]) for s, ri, hi in cells])
    worst = 0.0
    for (s, ri, hi), (score, _, _) in zip(cells, oracle):
        want = score - float(np.log(len(sites[s].haplotypes[hi])))
        got = float(results[s]["ll"][ri, hi])
        worst = max(worst, abs(got - want) / abs(want))
    check(worst <= 1e-4, f"forward log-likelihood rel. error {worst} > 1e-4")
    return dict(requests=len(sites), **counts,
                pair_jobs=len(sites) * sz["site_reads"] * 2,
                ll_checked=len(cells), ll_max_rel_err=worst)


def phase_mesh(traffic, sz, n_chips: int) -> dict:
    from repro.compat import make_mesh

    one, _ = serve_align(traffic, sz)
    mesh = make_mesh((n_chips,), ("data",))
    spread: list = []
    many, metrics = serve_align(traffic, sz, mesh=mesh, spread=spread)
    counts = reconcile(metrics, len(traffic))
    for i, (a, b) in enumerate(zip(one, many)):
        check(a == b, f"request {i}: mesh result {b} != one-device {a}")
    check(spread and all(len(d) == n_chips for d in spread),
          f"block outputs not spread over {n_chips} devices: {spread}")
    return dict(requests=len(traffic), **counts, blocks=len(spread),
                devices_per_block=sorted({len(d) for d in spread}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform, interpret-mode Pallas")
    args = ap.parse_args(argv)

    dev = device_info()
    on_tpu = dev["platform"] == "tpu"
    if not (on_tpu or args.rehearse):
        emit({"ok": False, "error": "no TPU found (use --rehearse to run "
              "tiny sizes on this platform)", "device": dev})
        return 1
    if dev["count"] < args.chips:
        emit({"ok": False, "error": f"--chips {args.chips} but "
              f"{dev['count']} devices", "device": dev})
        return 1
    cache_dir = compile_cache.enable()
    sz = REHEARSE if args.rehearse else FULL
    # the align, pallas and mesh phases share one request stream
    traffic = align_traffic(np.random.default_rng([args.seed, 0]), sz)
    if args.chips > 1:
        phases = [("mesh", lambda r: phase_mesh(traffic, sz, args.chips))]
    else:
        phases = [("align", lambda r: phase_align(traffic, sz)),
                  ("pallas", lambda r: phase_pallas(r, traffic, sz, on_tpu)),
                  ("map", lambda r: phase_map(r, sz, args.seed)),
                  ("genotype", lambda r: phase_genotype(r, sz, args.seed))]
    emit({"phase": "setup", "seed": args.seed, "rehearse": args.rehearse,
          "compile_cache": cache_dir, "device": dev})
    ok = True
    for k, (name, fn) in enumerate(phases):
        rng = np.random.default_rng([args.seed, k + 1])
        t0, c0 = time.perf_counter(), compile_seconds()
        try:
            out = fn(rng)
            passed, err = True, None
        except Exception as e:           # report every phase, then fail
            out, passed, err = {}, False, f"{type(e).__name__}: {e}"
        emit({"phase": name, "ok": passed, **out,
              "first_chip_run": {
                  "wall_s": time.perf_counter() - t0,
                  "plan_compile_s": compile_seconds() - c0},
              **({"error": err} if err else {})})
        ok &= passed
    emit({"ok": ok, "device": dev})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
