"""repro.tune: design space, cost pruning, table persistence, the
get_plan consultation hook, warm boot, and the option validators."""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro import tune
from repro.core import kernels_zoo
from repro.runtime import plan as plan_mod


@pytest.fixture(autouse=True)
def _isolate_table(monkeypatch):
    """No test may see the developer's env/table, and none may leak an
    installed table into the next."""
    monkeypatch.delenv(tune.ENV_VAR, raising=False)
    tune.set_table(None)
    yield
    tune.set_table(None)


@pytest.fixture(scope="module")
def linear():
    return kernels_zoo.make("global_linear")


# ---------------------------------------------------------------------------
# space: derived, validated, deduplicated
# ---------------------------------------------------------------------------
class TestSpace:
    def test_grid_derived_from_registry(self, linear):
        spec, _ = linear
        cands = tune.enumerate_space(spec, "wavefront")
        # 5 strips x {1,2,4} legal tb_packs for 2-bit pointers (8 needs
        # 8//8 >= ptr_bits and is dropped by the runtime validator)
        assert len(cands) == 15
        assert all(set(c) == {"strip", "tb_pack"} for c in cands)
        assert tune.default_options(spec, "wavefront") in cands

    def test_illegal_points_dropped(self):
        spec, _ = kernels_zoo.make("global_affine")   # 4-bit pointers
        cands = tune.enumerate_space(spec, "wavefront")
        assert cands
        assert all(c["tb_pack"] in (1, 2) for c in cands)

    def test_score_only_collapses_tb_axis(self):
        from repro.prob import kernels as prob_kernels
        spec = prob_kernels.cached_pairhmm()
        assert spec.traceback is None
        cands = tune.enumerate_space(spec, "wavefront")
        assert len(cands) == 5                        # strip axis only
        assert all(c["tb_pack"] == 1 for c in cands)

    def test_untunable_engine_is_empty(self, linear):
        spec, _ = linear
        assert tune.enumerate_space(spec, "reference") == []
        assert tune.tunable_names("myers") == []


# ---------------------------------------------------------------------------
# cost: prune before timing, default always survives
# ---------------------------------------------------------------------------
class TestCostRank:
    def test_default_always_kept(self, linear):
        spec, params = linear
        default = tune.default_options(spec, "wavefront")
        cands = [default, {"strip": 4, "tb_pack": 1},
                 {"strip": 8, "tb_pack": 1}, {"strip": 16, "tb_pack": 1}]
        kept, pruned = tune.rank(spec, params, "wavefront", (16, 16), 2,
                                 cands, default=default, top_k=1)
        assert any(s["options"] == default for s in kept)
        assert len(kept) + len(pruned) == len(cands)

    def test_predictions_are_finite_and_ranked(self, linear):
        spec, params = linear
        cands = [{"strip": 1, "tb_pack": 1}, {"strip": 8, "tb_pack": 1}]
        kept, _ = tune.rank(spec, params, "wavefront", (16, 16), None,
                            cands, top_k=4)
        rates = [s["predicted_cells_per_s"] for s in kept]
        assert all(np.isfinite(r) and r > 0 for r in rates)
        assert rates == sorted(rates, reverse=True)


# ---------------------------------------------------------------------------
# table: persistence, staleness, env semantics
# ---------------------------------------------------------------------------
class TestTable:
    def test_roundtrip(self, tmp_path):
        t = tune.TuningTable()
        t.record("global_linear", "wavefront", (64, 64), 8,
                 {"strip": 4, "tb_pack": 2}, cells_per_s=1e9)
        path = tmp_path / "t.json"
        t.save(path)
        loaded = tune.TuningTable.load(path)
        assert loaded.lookup_options("global_linear", "wavefront",
                                     (64, 64), 8) == \
            {"strip": 4, "tb_pack": 2}
        assert loaded.lookup_options("global_linear", "wavefront",
                                     (64, 64), 16) is None

    def test_stale_schema_refuses_to_load(self, tmp_path):
        path = tmp_path / "t.json"
        path.write_text(json.dumps({"schema": 999, "entries": {}}))
        with pytest.raises(ValueError, match="schema"):
            tune.TuningTable.load(str(path))

    def test_foreign_jax_version_never_matches(self):
        t = tune.TuningTable()
        key = tune.entry_key("k", "wavefront", (64, 64), 8,
                             jax_version="0.0.0-not-ours")
        t.entries[key] = {"options": {"strip": 16}}
        assert t.lookup_options("k", "wavefront", (64, 64), 8) is None

    def test_foreign_backend_never_matches(self):
        t = tune.TuningTable()
        foreign = tune.entry_key("k", "wavefront", (64, 64), 8,
                                 backend="tpu-not-ours")
        t.entries[foreign] = {"options": {"strip": 16}}
        # the backend is part of the key, so the entry is structurally
        # invisible here ...
        assert t.lookup_options("k", "wavefront", (64, 64), 8) is None
        # ... and re-keying the same point for *this* host matches again,
        # proving the miss above is the backend and nothing else
        native = tune.entry_key("k", "wavefront", (64, 64), 8)
        t.entries[native] = {"options": {"strip": 16}}
        assert t.lookup_options("k", "wavefront", (64, 64), 8) == \
            {"strip": 16}

    def test_env_off_disables_installed_table(self, monkeypatch):
        t = tune.TuningTable()
        tune.set_table(t)
        assert tune.active_table() is t
        monkeypatch.setenv(tune.ENV_VAR, "off")
        assert tune.active_table() is None

    def test_env_path_discovery(self, tmp_path, monkeypatch):
        t = tune.TuningTable()
        t.record("global_linear", "wavefront", (32, 32), 4, {"strip": 2})
        path = tmp_path / "env_table.json"
        t.save(path)
        monkeypatch.setenv(tune.ENV_VAR, str(path))
        assert tune.lookup("global_linear", "wavefront",
                           (32, 32), 4) == {"strip": 2}

    def test_default_lookup_costs_one_system_call(self, monkeypatch):
        """``get_plan`` looks the table up at every launch.  Through the
        default path a lookup makes one ``stat`` (the table's mtime
        check) and resolves no path: on a host-bound served path each
        system call can hand the interpreter lock to another thread."""
        import os
        tune.lookup("k", "wavefront", (32, 32), 4)
        calls = []
        stat, lstat = os.stat, os.lstat
        monkeypatch.setattr(os, "stat", lambda p, *a, **k: (
            calls.append(str(p)), stat(p, *a, **k))[1])
        monkeypatch.setattr(os, "lstat", lambda p, *a, **k: (
            calls.append(str(p)), lstat(p, *a, **k))[1])
        for _ in range(5):
            tune.lookup("k", "wavefront", (32, 32), 4)
        assert calls == [str(tune.default_path())] * 5

    def test_corrupt_table_is_no_table(self, tmp_path, monkeypatch):
        path = tmp_path / "bad.json"
        path.write_text("{not json")
        monkeypatch.setenv(tune.ENV_VAR, str(path))
        assert tune.lookup("k", "wavefront", (32, 32), 4) is None


# ---------------------------------------------------------------------------
# the get_plan hook
# ---------------------------------------------------------------------------
class TestGetPlanConsultsTable:
    def _tuned_table(self):
        t = tune.TuningTable()
        t.record("global_linear", "wavefront", (32, 32), 4,
                 {"strip": 8, "tb_pack": 2})
        return t

    def test_table_sets_defaults(self, linear):
        spec, _ = linear
        tune.set_table(self._tuned_table())
        plan_mod.clear_plan_cache(keep_stats=True)
        key = plan_mod.get_plan(spec, "wavefront", (32,), (32,),
                                batch_size=4).key
        assert (key.strip, key.tb_pack) == (8, 2)

    def test_explicit_options_beat_table(self, linear):
        spec, _ = linear
        tune.set_table(self._tuned_table())
        plan_mod.clear_plan_cache(keep_stats=True)
        key = plan_mod.get_plan(spec, "wavefront", (32,), (32,),
                                batch_size=4, strip=1).key
        # any explicit option opts the whole request out of the table
        assert key.strip == 1
        assert key.tb_pack == spec.tb_pack

    def test_env_off_restores_hand_picked_exactly(self, linear,
                                                  monkeypatch):
        spec, _ = linear
        plan_mod.clear_plan_cache(keep_stats=True)
        baseline = plan_mod.get_plan(spec, "wavefront", (32,), (32,),
                                     batch_size=4).key
        tune.set_table(self._tuned_table())
        monkeypatch.setenv(tune.ENV_VAR, "off")
        plan_mod.clear_plan_cache(keep_stats=True)
        key = plan_mod.get_plan(spec, "wavefront", (32,), (32,),
                                batch_size=4).key
        assert key == baseline

    def test_unmatched_point_uses_defaults(self, linear):
        spec, _ = linear
        tune.set_table(self._tuned_table())
        plan_mod.clear_plan_cache(keep_stats=True)
        baseline_strip = plan_mod.resolve_engine_options(
            spec, "wavefront", {})["strip"]
        key = plan_mod.get_plan(spec, "wavefront", (64,), (64,),
                                batch_size=4).key
        assert key.strip == baseline_strip

    def test_backend_mismatch_falls_back_to_defaults(self, linear):
        # a table recorded on another backend/jax build must not steer
        # this host's plans — get_plan silently falls back to defaults
        spec, _ = linear
        t = tune.TuningTable()
        key = tune.entry_key("global_linear", "wavefront", (32, 32), 4,
                             backend="tpu-not-ours", jax_version="9.9.9")
        t.entries[key] = {"options": {"strip": 16, "tb_pack": 4}}
        tune.set_table(t)
        plan_mod.clear_plan_cache(keep_stats=True)
        baseline = plan_mod.resolve_engine_options(spec, "wavefront", {})
        got = plan_mod.get_plan(spec, "wavefront", (32,), (32,),
                                batch_size=4).key
        assert (got.strip, got.tb_pack) == \
            (baseline["strip"], baseline["tb_pack"])
        assert (got.strip, got.tb_pack) != (16, 4)


# ---------------------------------------------------------------------------
# option validators (plan-key construction errors name the option)
# ---------------------------------------------------------------------------
class TestValidators:
    @pytest.mark.parametrize("req,name", [
        ({"strip": 0}, "strip"),
        ({"strip": 1.5}, "strip"),
        ({"strip": True}, "strip"),
        ({"strip": "4"}, "strip"),
        ({"xdrop": -1}, "xdrop"),
        ({"xdrop": 2.5}, "xdrop"),
        ({"tb_pack": 1.0}, "tb_pack"),
    ])
    def test_bad_values_name_the_option(self, linear, req, name):
        spec, _ = linear
        with pytest.raises(ValueError, match=name):
            plan_mod.resolve_engine_options(spec, "wavefront", req)

    def test_pow2_validator(self):
        assert plan_mod.validate_pow2_option("screen_block", 64) == 64
        with pytest.raises(ValueError, match="screen_block"):
            plan_mod.validate_pow2_option("screen_block", 48)
        with pytest.raises(ValueError, match="screen_block"):
            plan_mod.validate_pow2_option("screen_block", 0)

    def test_mapper_rejects_bad_screen_block(self):
        from repro.mapping import ReadMapper
        ref = np.random.default_rng(0).integers(
            0, 4, 256).astype(np.uint8)
        with pytest.raises(ValueError, match="screen_block"):
            ReadMapper(ref, screen_block=48)


# ---------------------------------------------------------------------------
# cache stats history (clear_plan_cache keep_stats)
# ---------------------------------------------------------------------------
class TestCacheStatsHistory:
    def test_keep_stats_rolls_totals(self, linear):
        spec, params = linear
        plan_mod.clear_plan_cache()           # zero everything
        plan = plan_mod.get_plan(spec, "wavefront", (16,), (16,),
                                 batch_size=2, with_traceback=False,
                                 mode="fill")
        data = tune.make_batch(np.random.default_rng(0), spec,
                               (16, 16), 2)
        plan(params, *data)
        before = plan_mod.plan_cache_info()["totals"]
        assert before["compiled"] == 1 and before["compile_s"] > 0
        plan_mod.clear_plan_cache(keep_stats=True)
        after = plan_mod.plan_cache_info()["totals"]
        assert after["plans"] == before["plans"]
        assert after["compiled"] == 1
        assert after["compile_s"] == pytest.approx(before["compile_s"])
        assert plan_mod.plan_cache_info()["size"] == 0
        plan_mod.clear_plan_cache()           # full reset drops history
        assert plan_mod.plan_cache_info()["totals"]["compiled"] == 0


# ---------------------------------------------------------------------------
# search: parity + winner >= default, sweep -> table
# ---------------------------------------------------------------------------
class TestSearch:
    def test_tune_point_winner_matches_or_beats_default(self, linear,
                                                        monkeypatch):
        monkeypatch.setenv(tune.ENV_VAR, "off")
        spec, params = linear
        res = tune.tune_point(spec, params, "wavefront", (16, 16), 2,
                              top_k=2, iters=1)
        assert res["speedup_vs_default"] >= 1.0
        assert res["options"] in tune.enumerate_space(spec, "wavefront")
        measured = {tuple(sorted(m["options"].items()))
                    for m in res["measurements"]}
        assert tuple(sorted(res["default_options"].items())) in measured

    def test_tune_point_nothing_to_tune(self):
        spec, params = kernels_zoo.make("edit_distance")
        assert tune.tune_point(spec, params, "myers", (32, 32), 2) is None

    def test_parity_catches_score_mismatch(self, linear):
        spec, _ = linear
        from repro.core.types import Alignment
        a = Alignment(score=np.float32(1.0), end_i=np.int32(1),
                      end_j=np.int32(1))
        b = Alignment(score=np.float32(2.0), end_i=np.int32(1),
                      end_j=np.int32(1))
        tune.assert_parity(spec, a, a)
        with pytest.raises(AssertionError):
            tune.assert_parity(spec, a, b)

    def test_run_sweep_records_and_skips(self, monkeypatch):
        monkeypatch.setenv(tune.ENV_VAR, "off")
        points = [("global_linear", "wavefront", (16, 16), 2),
                  ("edit_distance", "myers", (16, 16), 2)]   # untunable
        table = tune.run_sweep(points, top_k=2, iters=1)
        assert len(table) == 1
        opts = table.lookup_options("global_linear", "wavefront",
                                    (16, 16), 2)
        assert set(opts) == {"strip", "tb_pack"}


# ---------------------------------------------------------------------------
# warm boot
# ---------------------------------------------------------------------------
class TestWarm:
    def test_warm_plan_compiles_once(self, linear):
        spec, params = linear
        plan_mod.clear_plan_cache()
        plan = tune.warm_plan(spec, params, "wavefront", (16,), (16,),
                              batch_size=2)
        assert plan.compile_s is not None
        calls = plan.calls
        again = tune.warm_plan(spec, params, "wavefront", (16,), (16,),
                               batch_size=2)
        assert again is plan and again.calls == calls   # no re-dispatch

    def test_alignment_service_warm_start(self):
        from repro.serve import AlignRequest, AlignmentService
        plan_mod.clear_plan_cache()
        svc = AlignmentService(max_len=32, block=2,
                               warm_start=[("global_linear", 32)])
        compiled = plan_mod.plan_cache_info()["totals"]["compiled"]
        assert compiled >= 1
        rng = np.random.default_rng(1)
        fut = svc.submit(AlignRequest(
            rid=0, kernel="global_linear",
            query=rng.integers(0, 4, 20).astype(np.uint8),
            ref=rng.integers(0, 4, 24).astype(np.uint8)))
        assert fut.result()["score"] is not None
        after = plan_mod.plan_cache_info()["totals"]["compiled"]
        assert after == compiled        # first request hit the warm plan

    def test_genotyping_service_warm_start(self):
        from repro.serve import GenotypeRequest, GenotypingService
        plan_mod.clear_plan_cache()
        svc = GenotypingService(max_len=32, block=2,
                                warm_start=[(20, 24)])
        compiled = plan_mod.plan_cache_info()["totals"]["compiled"]
        assert compiled >= 1
        rng = np.random.default_rng(2)
        hap = rng.integers(0, 4, 24).astype(np.uint8)
        fut = svc.submit(GenotypeRequest(
            rid=0, reads=[hap[:20].copy()], haplotypes=[hap]))
        assert "ll" in fut.result()
        after = plan_mod.plan_cache_info()["totals"]["compiled"]
        assert after == compiled

    def test_mapping_service_warm_start(self):
        from repro.serve import ReadMappingService
        plan_mod.clear_plan_cache()
        rng = np.random.default_rng(3)
        ref = rng.integers(0, 4, 512).astype(np.uint8)
        svc = ReadMappingService(ref, block=2,
                                 warm_start=[(64, 128, 32)])
        info = plan_mod.plan_cache_info()
        assert info["totals"]["compiled"] >= 2   # extension + screen
        keys = {(k.kernel, k.bucket_shape) for k in info["keys"]}
        assert any(b == (((64,), (128,))) for _, b in keys)
