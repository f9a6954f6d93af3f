"""The unified alignment runtime: registry, plan cache, bucketing, and
traceback-layout parity."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import align, kernels_zoo
from repro.core import types as T
from repro.core.traceback import _make_reader
from repro.runtime import (available_engines, bucket_length, bucket_shape,
                           get_engine, inverse_permutation, pack_by_bucket,
                           pad_to_bucket, register_engine)
from repro.runtime import plan as plan_mod

from conftest import seed_plan


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_all_builtin_engines_resolve():
    for name in ("reference", "wavefront", "banded", "pallas",
                 "pallas_interpret"):
        assert name in available_engines()
        assert callable(get_engine(name))


def test_unknown_engine_raises():
    with pytest.raises(ValueError, match="unknown engine"):
        get_engine("systolic_fpga")


def test_plug_in_engine(rng):
    calls = []

    def counting_engine(spec, params, query, ref, q_len=None, r_len=None):
        calls.append(spec.name)
        return get_engine("reference")(spec, params, query, ref, q_len, r_len)

    register_engine("counting", counting_engine, overwrite=True)
    spec, params = kernels_zoo.make("global_linear")
    import jax.numpy as jnp
    q = jnp.asarray(rng.integers(0, 4, 20).astype(np.uint8))
    a = align(spec, params, q, q, engine_name="counting",
              with_traceback=False)
    b = align(spec, params, q, q, engine_name="reference",
              with_traceback=False)
    assert calls == ["global_linear"]
    assert int(a.score) == int(b.score)


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------
def test_same_bucket_reuses_one_plan(rng):
    """Two align calls with the same (kernel, engine, bucket) share one
    CompiledPlan: the cache holds exactly one entry."""
    import jax.numpy as jnp
    spec, params = kernels_zoo.make("global_affine")
    plan_mod.clear_plan_cache()
    q1 = jnp.asarray(rng.integers(0, 4, 40).astype(np.uint8))
    r1 = jnp.asarray(rng.integers(0, 4, 44).astype(np.uint8))
    q2 = jnp.asarray(rng.integers(0, 4, 50).astype(np.uint8))
    r2 = jnp.asarray(rng.integers(0, 4, 61).astype(np.uint8))
    align(spec, params, q1, r1)            # lengths 40/44 -> bucket 64/64
    info1 = plan_mod.plan_cache_info()
    align(spec, params, q2, r2)            # lengths 50/61 -> same bucket
    info2 = plan_mod.plan_cache_info()
    assert info1["size"] == 1
    assert info2["size"] == 1, info2["keys"]
    assert info2["hits"] == info1["hits"] + 1
    key = info2["keys"][0]
    assert key.kernel == "global_affine"
    assert key.bucket_shape == ((64,), (64,))


def test_mesh_axis_ignored_for_local_plans(rng):
    """Without a mesh, mesh_axis must not split the cache."""
    spec, params = kernels_zoo.make("global_linear")
    plan_mod.clear_plan_cache()
    p1 = plan_mod.get_plan(spec, "wavefront", (16,), (16,), batch_size=4)
    p2 = plan_mod.get_plan(spec, "wavefront", (16,), (16,), batch_size=4,
                           mesh_axis="x")
    assert p1 is p2
    assert plan_mod.plan_cache_info()["size"] == 1


def test_distinct_engines_get_distinct_plans(rng):
    import jax.numpy as jnp
    spec, params = kernels_zoo.make("global_linear")
    plan_mod.clear_plan_cache()
    q = jnp.asarray(rng.integers(0, 4, 20).astype(np.uint8))
    s_wf = align(spec, params, q, q, engine_name="wavefront",
                 with_traceback=False).score
    s_ref = align(spec, params, q, q, engine_name="reference",
                  with_traceback=False).score
    assert plan_mod.plan_cache_info()["size"] == 2
    assert int(s_wf) == int(s_ref)


def test_tiling_reuses_plans_across_calls(rng):
    from repro.core.tiling import tiled_align
    spec, params = kernels_zoo.make("global_affine")
    from repro.core import alphabets
    ref = alphabets.random_dna(rng, 120)
    read = alphabets.mutate(rng, ref, 0.1)
    plan_mod.clear_plan_cache()
    tiled_align(spec, params, read, ref, tile=64, overlap=16)
    n1 = plan_mod.plan_cache_info()["size"]
    tiled_align(spec, params, read[:100], ref[:110], tile=64, overlap=16)
    n2 = plan_mod.plan_cache_info()["size"]
    assert n1 == 2          # interior + final variants
    assert n2 == 2          # second call compiled nothing new


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------
def test_bucket_length_choices():
    assert bucket_length(0) == 16
    assert bucket_length(1) == 16
    assert bucket_length(16) == 16
    assert bucket_length(17) == 32
    assert bucket_length(40) == 64
    assert bucket_length(64) == 64
    assert bucket_length(200, max_bucket=256) == 256
    assert bucket_length(40, min_bucket=8, growth=4.0) == 128
    with pytest.raises(ValueError):
        bucket_length(300, max_bucket=256)
    assert bucket_shape(10, 40) == (16, 64)


def test_bucket_length_cap_snaps_to_grid():
    """An off-grid ``max_bucket`` must never become a bucket shape: the
    cap snaps down to the largest grid bucket and lengths above it raise
    (regression: ``min(b, max_bucket)`` leaked a 100-wide shape into the
    plan cache, silently splitting it)."""
    assert bucket_length(60, max_bucket=100) == 64   # unaffected below cap
    with pytest.raises(ValueError, match="largest bucket 64"):
        bucket_length(80, max_bucket=100)            # pre-fix: returned 100
    with pytest.raises(ValueError):
        bucket_length(100, max_bucket=100)
    # on-grid caps behave exactly as before
    assert bucket_length(200, max_bucket=256) == 256
    assert bucket_shape(10, 60, max_bucket=100) == (16, 64)
    with pytest.raises(ValueError):
        pack_by_bucket([(80, 10)], max_bucket=100)
    with pytest.raises(ValueError, match="below min_bucket"):
        bucket_length(5, min_bucket=16, max_bucket=10)


def test_run_pairs_pipelined_matches_sync(rng):
    """Pipelined packed dispatch returns bit-identical results in input
    order for every depth."""
    from repro.runtime import run_pairs
    spec, params = kernels_zoo.make("global_affine")
    pairs = [(rng.integers(0, 4, int(rng.integers(10, 90))).astype(np.uint8),
              rng.integers(0, 4, int(rng.integers(10, 90))).astype(np.uint8))
             for _ in range(13)]
    outs = {d: run_pairs(spec, params, pairs, block=4, pipeline_depth=d)
            for d in (1, 2, 4)}
    for d in (2, 4):
        for a, b in zip(outs[d], outs[1]):
            assert float(a.score) == float(b.score)
            np.testing.assert_array_equal(a.moves, b.moves)
            assert int(a.n_moves) == int(b.n_moves)


def test_run_pipelined_depth_and_abandon():
    from repro.runtime import run_pipelined
    events = []
    total = run_pipelined(
        range(4), lambda i: i * 10,
        lambda i, out: events.append(("h", i, out)) or 1, depth=2)
    assert total == 4
    assert events == [("h", 0, 0), ("h", 1, 10), ("h", 2, 20), ("h", 3, 30)]
    abandoned = []
    with pytest.raises(RuntimeError):
        run_pipelined(
            range(4), lambda i: i,
            lambda i, out: (_ for _ in ()).throw(RuntimeError("boom")),
            depth=3, on_abandon=lambda i, out: abandoned.append(i))
    assert abandoned == [1, 2]        # launched-but-unharvested window
    with pytest.raises(ValueError, match="depth"):
        run_pipelined([], lambda i: i, lambda i, o: None, depth=0)


def test_pad_to_bucket_roundtrip(rng):
    x = rng.integers(0, 4, (37, 5)).astype(np.uint8)
    p = pad_to_bucket(x, 64)
    assert p.shape == (64, 5)
    np.testing.assert_array_equal(p[:37], x)
    assert not p[37:].any()
    assert pad_to_bucket(x, 37) is x
    with pytest.raises(ValueError):
        pad_to_bucket(x, 16)


@pytest.mark.parametrize("block", [1, 3, 8, None])
def test_pack_by_bucket_inverse_restores_order(block, rng):
    lengths = [(int(rng.integers(1, 200)), int(rng.integers(1, 200)))
               for _ in range(23)]
    batches, inv = pack_by_bucket(lengths, block=block)
    order = [int(i) for b in batches for i in b.indices]
    assert sorted(order) == list(range(len(lengths)))     # a permutation
    for b in batches:
        assert block is None or len(b.indices) <= block
        for i in b.indices:
            ql, rl = lengths[i]
            assert ql <= b.bucket[0] and rl <= b.bucket[1]
    packed = [lengths[i] for i in order]                  # packed order
    restored = [packed[inv[i]] for i in range(len(lengths))]
    assert restored == lengths
    np.testing.assert_array_equal(inverse_permutation(np.asarray(order)),
                                  inv)


# ---------------------------------------------------------------------------
# traceback-layout parity: the ('chunk', n_pe, pack) and ('diag', pack)
# word readers must reproduce the 'row' reader on identical pointers
# ---------------------------------------------------------------------------
def _chunk_words(row, Q, R, n_pe, pack):
    """The Pallas ('chunk', n_pe, pack) store of a row-major pointer
    matrix: wavefront w of lane l in word w // per_word, slot w % per_word."""
    from repro.core.traceback import word_layout
    width, per_word = word_layout(pack)
    n_groups = -(-(n_pe + R - 1) // per_word)
    out = np.zeros((-(-Q // n_pe), n_groups, n_pe), np.int64)
    for i in range(1, Q + 1):
        for j in range(1, R + 1):
            c, lane = (i - 1) // n_pe, (i - 1) % n_pe
            g, slot = divmod(lane + j - 1, per_word)
            out[c, g, lane] |= int(row[i, j]) << (slot * width)
    return out.astype(np.uint32).view(np.int32)


def _diag_words(row, Q, R, pack):
    """The wavefront ('diag', pack) store of a row-major pointer matrix."""
    import jax.numpy as jnp
    from repro.core.traceback import pack_words
    diag = np.zeros((Q + R, Q + 1), np.uint8)
    for i in range(1, Q + 1):
        for j in range(1, R + 1):
            diag[i + j - 1, i] = row[i, j]
    return np.asarray(pack_words(jnp.asarray(diag), pack))


@pytest.mark.parametrize("Q,R,n_pe", [(8, 8, 4), (10, 7, 4),   # Q % n_pe != 0
                                      (5, 12, 8), (13, 13, 8)])
def test_tb_reader_layout_parity(Q, R, n_pe, rng):
    row = np.zeros((Q + 1, R + 1), np.uint8)
    row[1:, 1:] = rng.integers(0, 7, (Q, R)).astype(np.uint8)

    import jax.numpy as jnp
    readers = {
        "row": _make_reader(jnp.asarray(row), "row"),
        "diag": _make_reader(jnp.asarray(_diag_words(row, Q, R, 1)),
                             ("diag", 1)),
        "chunk": _make_reader(jnp.asarray(_chunk_words(row, Q, R, n_pe, 1)),
                              ("chunk", n_pe, 1)),
    }
    for i in range(1, Q + 1):
        for j in range(1, R + 1):
            got = {k: int(f(i, j)) for k, f in readers.items()}
            assert got["chunk"] == got["row"] == got["diag"], (i, j, got)


@pytest.mark.parametrize("pack", [2, 4])
def test_packed_tb_readers_match_unpacked(pack, rng):
    """The ('diag', pack) and ('chunk', n_pe, pack) readers must decode
    the packed stores to exactly the unpacked pointer values."""
    import jax.numpy as jnp
    Q, R, n_pe = 9, 11, 4
    width = 8 // pack
    row = np.zeros((Q + 1, R + 1), np.uint8)
    row[1:, 1:] = rng.integers(0, 1 << width, (Q, R)).astype(np.uint8)
    readers = {
        "row": _make_reader(jnp.asarray(row), "row"),
        "diag_p": _make_reader(jnp.asarray(_diag_words(row, Q, R, pack)),
                               ("diag", pack)),
        "chunk_p": _make_reader(
            jnp.asarray(_chunk_words(row, Q, R, n_pe, pack)),
            ("chunk", n_pe, pack)),
    }
    for i in range(1, Q + 1):
        for j in range(1, R + 1):
            got = {k: int(f(i, j)) for k, f in readers.items()}
            assert got["diag_p"] == got["chunk_p"] == got["row"], (i, j, got)


def test_plan_cache_keys_schedule_options(rng):
    """strip/tb_pack join the cache key: explicit seed knobs and the
    defaults compile distinct executables; defaults are deterministic."""
    spec, params = kernels_zoo.make("global_linear")
    plan_mod.clear_plan_cache()
    p_dflt = plan_mod.get_plan(spec, "wavefront", (16,), (16,))
    p_dflt2 = plan_mod.get_plan(spec, "wavefront", (16,), (16,))
    p_seed = plan_mod.get_plan(spec, "wavefront", (16,), (16,),
                               strip=1, tb_pack=1)
    assert p_dflt is p_dflt2
    assert p_dflt.key.tb_pack == spec.tb_pack == 4
    assert (p_seed.key.strip, p_seed.key.tb_pack) == (1, 1)
    if p_dflt.key.strip == 1 and p_dflt.key.tb_pack == 1:
        assert p_dflt is p_seed
    else:
        assert p_dflt is not p_seed
    with pytest.raises(ValueError, match="tb_pack"):
        plan_mod.get_plan(spec, "wavefront", (16,), (16,), tb_pack=3)
    # affine pointers need 4 bits: pack 4 leaves 2-bit slots
    spec_a, _ = kernels_zoo.make("global_affine")
    with pytest.raises(ValueError, match="tb_pack"):
        plan_mod.get_plan(spec_a, "wavefront", (16,), (16,), tb_pack=4)


def test_traceback_bytes_estimator():
    """Packed stores shrink by the kernel's tb_pack; score-only kernels
    occupy nothing."""
    spec_l, _ = kernels_zoo.make("global_linear")    # 2-bit -> pack 4
    spec_a, _ = kernels_zoo.make("global_affine")    # 4-bit -> pack 2
    spec_v, _ = kernels_zoo.make("viterbi_pairhmm")  # no traceback
    seed_l = plan_mod.traceback_bytes(spec_l, 256, 256, strip=1, tb_pack=1)
    opt_l = plan_mod.traceback_bytes(spec_l, 256, 256, strip=1)
    opt_a = plan_mod.traceback_bytes(spec_a, 256, 256, strip=1)
    assert seed_l == 512 * 65 * 4          # 257 lanes in 65 int32 words
    assert seed_l / opt_l == pytest.approx(4.0, rel=0.05)
    assert seed_l / opt_a == pytest.approx(2.0, rel=0.05)
    assert plan_mod.traceback_bytes(spec_v, 256, 256) == 0


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("bucket", [64, 128, 256, 512])
@pytest.mark.parametrize("engine", ["wavefront", "pallas_interpret"])
def test_plan_defaults_match_seed_schedule(engine, bucket, batch):
    """The plan cache's defaults (backend strip, packed pointers, the
    fill's early exit at the block's length bound, the batched
    traceback) give bit-identical alignments to the seed schedule, over
    two length-sorted blocks of lengths in (bucket/2, bucket] — the
    blocks the service's bucket queues form, longest first."""
    rng = np.random.default_rng(bucket + batch)
    spec, params = kernels_zoo.make("global_affine")
    total = 2 * batch
    qs = rng.integers(0, 4, (total, bucket)).astype(np.uint8)
    rs = rng.integers(0, 4, (total, bucket)).astype(np.uint8)
    ql = rng.integers(bucket // 2 + 1, bucket + 1, total).astype(np.int32)
    rl = rng.integers(bucket // 2 + 1, bucket + 1, total).astype(np.int32)
    order = np.argsort(-(ql.astype(np.int64) + rl), kind="stable")
    seed = seed_plan(spec, engine, bucket)
    plan = plan_mod.get_plan(spec, engine, (bucket,), (bucket,),
                             batch_size=batch)
    for sel in np.split(order, 2):
        block = (qs[sel], rs[sel], ql[sel], rl[sel])
        a, b = seed(params, *block), plan(params, *block)
        for f in ("score", "end_i", "end_j", "start_i", "start_j",
                  "n_moves", "moves"):
            np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                          np.asarray(getattr(b, f)),
                                          err_msg=f)


# ---------------------------------------------------------------------------
# service: per-(kernel, bucket) padding instead of one global max_len
# ---------------------------------------------------------------------------
def test_service_pads_to_bucket_not_max_len(rng):
    from repro.serve import AlignRequest, AlignmentService
    svc = AlignmentService(max_len=256, block=4)
    short = [(rng.integers(0, 4, 12).astype(np.uint8),
              rng.integers(0, 4, 14).astype(np.uint8)) for _ in range(4)]
    long = [(rng.integers(0, 4, 180).astype(np.uint8),
             rng.integers(0, 4, 200).astype(np.uint8)) for _ in range(2)]
    reqs = [AlignRequest(rid=i, kernel="global_affine", query=q, ref=r)
            for i, (q, r) in enumerate(short + long)]
    for r in reqs:
        svc.submit(r)
    # queues are keyed per (kernel, bucket), not per kernel
    assert set(svc.queues) == {("global_affine", (16, 16)),
                               ("global_affine", (256, 256))}
    assert svc.drain() == 6
    buckets = {d["bucket"] for d in svc.dispatches}
    assert (16, 16) in buckets           # short batch padded to its bucket
    assert all(b <= (256, 256) for b in buckets)
    # results match the direct (unbatched, unpadded) path
    import jax.numpy as jnp
    spec, params = kernels_zoo.make("global_affine")
    for req in reqs:
        direct = align(spec, params, jnp.asarray(req.query),
                       jnp.asarray(req.ref), with_traceback=False)
        assert req.result["score"] == pytest.approx(float(direct.score))


def _mixed_bucket_requests(rng):
    """2 short (bucket 16), 2 medium (64), 2 large (256) requests."""
    from repro.serve import AlignRequest
    sizes = [12, 14, 40, 50, 180, 200]
    return [AlignRequest(rid=i, kernel="global_affine",
                         query=rng.integers(0, 4, s).astype(np.uint8),
                         ref=rng.integers(0, 4, s).astype(np.uint8))
            for i, s in enumerate(sizes)]


def test_service_coalesces_partial_batches_across_buckets(rng):
    """A trailing partial batch tops up from the next-larger bucket; every
    request still gets its own correct result (order restoration)."""
    from repro.serve import AlignRequest, AlignmentService  # noqa: F811
    import jax.numpy as jnp
    svc = AlignmentService(max_len=256, block=4)
    reqs = _mixed_bucket_requests(rng)
    for r in reqs:
        svc.submit(r)
    assert svc.drain() == 6
    dispatches = list(svc.dispatches)
    # shorts coalesce with mediums at (64, 64); larges stay partial alone
    assert len(dispatches) == 2
    assert dispatches[0]["bucket"] == (64, 64)
    assert dispatches[0]["n"] == 4 and dispatches[0]["coalesced"]
    assert dispatches[1]["bucket"] == (256, 256)
    assert not dispatches[1]["coalesced"]
    # per-request results survive the reshuffle and match the direct path
    spec, params = kernels_zoo.make("global_affine")
    for req in reqs:
        direct = align(spec, params, jnp.asarray(req.query),
                       jnp.asarray(req.ref), with_traceback=False)
        assert req.result["score"] == pytest.approx(float(direct.score))


def test_service_coalescing_off_keeps_per_bucket_batches(rng):
    from repro.serve import AlignRequest, AlignmentService  # noqa: F811
    svc = AlignmentService(max_len=256, block=4, coalesce=False)
    for r in _mixed_bucket_requests(rng):
        svc.submit(r)
    assert svc.drain() == 6
    assert len(svc.dispatches) == 3
    assert all(not d["coalesced"] for d in svc.dispatches)


def test_service_budget_sized_blocks(rng):
    """With a traceback-memory budget the service launches as many
    alignments per bucket as the packed store admits — one big batch
    instead of many fixed-size ones — and results stay correct."""
    from repro.serve import AlignRequest, AlignmentService  # noqa: F811
    import jax.numpy as jnp
    spec, params = kernels_zoo.make("global_affine")
    per = plan_mod.traceback_bytes(spec, 16, 16)
    svc = AlignmentService(max_len=64, block=2, coalesce=False,
                           tb_budget_bytes=8 * per, max_block=16)
    assert svc.block_for("global_affine", (16, 16)) == 8
    # the same budget admits fewer rows at a bigger bucket ...
    assert svc.block_for("global_affine", (64, 64)) < 8
    # ... and pack-4 linear kernels get more rows than pack-2 affine
    per_l = plan_mod.traceback_bytes(
        kernels_zoo.make("global_linear")[0], 16, 16)
    assert svc.block_for("global_linear", (16, 16)) >= \
        svc.block_for("global_affine", (16, 16))
    assert per_l < per
    reqs = [AlignRequest(rid=i, kernel="global_affine",
                         query=rng.integers(0, 4, 12).astype(np.uint8),
                         ref=rng.integers(0, 4, 12).astype(np.uint8))
            for i in range(8)]
    for r in reqs:
        svc.submit(r)
    assert svc.drain() == 8
    assert len(svc.dispatches) == 1           # one budget-sized launch
    assert svc.dispatches[0]["n"] == 8
    for req in reqs:
        direct = align(spec, params, jnp.asarray(req.query),
                       jnp.asarray(req.ref), with_traceback=False)
        assert req.result["score"] == pytest.approx(float(direct.score))


@pytest.mark.parametrize("kernel,ratio", [("global_linear", 4),
                                          ("global_affine", 2)])
def test_service_budget_rows_grow_with_packing(kernel, ratio):
    """At a fixed traceback budget and a 4 kb bucket, packed pointers let
    one launch carry at least ``ratio`` times the rows the unpacked seed
    store admits: 4x for 2-bit linear pointers, 2x for 4-bit affine."""
    from repro.serve import AlignmentService
    budget, bucket = 256 << 20, 4096
    spec, _ = kernels_zoo.make(kernel)
    seed_rows = budget // plan_mod.traceback_bytes(spec, bucket, bucket,
                                                   strip=1, tb_pack=1)
    svc = AlignmentService(max_len=bucket, block=1, tb_budget_bytes=budget,
                           max_block=1 << 16)
    assert seed_rows >= 1
    assert svc.block_for(kernel, (bucket, bucket)) >= ratio * seed_rows


def test_resolve_engine_opts_shim_warns_and_matches():
    # legacy alias: same resolution, plus a DeprecationWarning nudging
    # callers to resolve_engine_options
    spec, _ = kernels_zoo.make("global_linear")
    with pytest.warns(DeprecationWarning, match="resolve_engine_options"):
        legacy = plan_mod.resolve_engine_opts(spec, "wavefront", strip=4)
    full = plan_mod.resolve_engine_options(spec, "wavefront", {"strip": 4})
    assert legacy == (full["strip"], full["tb_pack"])


# ---------------------------------------------------------------------------
# the plan program's named phases
# ---------------------------------------------------------------------------
def _op_name_parts(text):
    import re
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


def _ran_plan(engine, batch, rng):
    import jax.numpy as jnp
    spec, params = kernels_zoo.make("local_affine")
    plan = plan_mod.get_plan(spec, engine, (16,), (16,), batch_size=batch)
    shape = (16,) if batch is None else (batch, 16)
    q = jnp.asarray(rng.integers(0, 4, shape).astype(np.uint8))
    n = jnp.full(shape[:-1], 14, jnp.int32)
    plan(params, q, q, n, n)
    return plan


@pytest.mark.parametrize("engine,batch", [("wavefront", 2),
                                          ("pallas_interpret", 2),
                                          ("wavefront", None)])
def test_plan_program_names_its_phases(engine, batch, rng):
    """The compiled program's op_name metadata names the fill and the
    traceback, so a profiler trace can split device time between them."""
    parts = _op_name_parts(_ran_plan(engine, batch, rng).compiled_text())
    assert {plan_mod.FILL_SCOPE, plan_mod.TRACEBACK_SCOPE} <= parts


def test_phase_scopes_leave_instruction_names(rng, monkeypatch):
    """The scopes are metadata only: the compiled instructions, whose
    names a trace shows, are those of the program without them."""
    import contextlib
    import re
    import jax

    def names(text):
        return re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) =", text, re.M)

    scoped = _ran_plan("wavefront", 2, rng)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = plan_mod.CompiledPlan(scoped.key, scoped.spec, "wavefront")
    plain._avals = scoped._avals
    assert names(plain.compiled_text()) == names(scoped.compiled_text())
    assert plan_mod.FILL_SCOPE not in _op_name_parts(plain.compiled_text())


def test_compiled_text_needs_a_first_call():
    spec, _ = kernels_zoo.make("local_affine")
    cached = plan_mod.get_plan(spec, "wavefront", (8,), (8,), batch_size=2)
    plan = plan_mod.CompiledPlan(cached.key, spec, "wavefront")
    with pytest.raises(RuntimeError, match="has not run"):
        plan.compiled_text()
