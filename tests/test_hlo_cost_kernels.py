"""hlo_cost against *real* HLO of the wavefront and Myers fills.

The cost model's unit tests exercise synthetic HLO text; these pin it
to the genuine article in both dialects:

* compiled text (``compiled.as_text()``): XLA:CPU annotates while loops
  with ``known_trip_count`` when the bound is static — trip extraction
  must be *exact* there, and trips x diagonal width must land within 2x
  of the analytic cell count;
* lowered text (``lowered.compiler_ir('hlo').as_hlo_text()``): no ``%``
  sigils, bare computation headers, no trip annotations — the dialect
  the autotuner's pre-compile ranking reads (``analyze_plan``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import pytest

from repro.core import kernels_zoo
from repro.launch import hlo_cost, roofline
from repro.runtime import plan as plan_mod
from repro.runtime import registry

Q, R = 64, 128


def _compiled_fill_text(spec, params, engine_name, q, r, **opts):
    """Compiled (optimized) HLO text of a single-pair fill with every
    loop bound static (no dynamic live_bound), so XLA can annotate
    known_trip_count."""
    eng = functools.partial(registry.get_engine(engine_name), **opts)
    fn = jax.jit(functools.partial(plan_mod.fill_impl, spec, eng))
    comp = fn.lower(
        params,
        jax.ShapeDtypeStruct((q,), jnp.uint8),
        jax.ShapeDtypeStruct((r,), jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    return comp.as_text()


@pytest.fixture(scope="module")
def linear():
    return kernels_zoo.make("global_linear")


@pytest.fixture(scope="module")
def wavefront_costs(linear):
    """(strip -> (Cost, breakdown rows)) of the compiled wavefront fill
    at a static full-bucket live_bound."""
    spec, params = linear
    out = {}
    for strip in (1, 4):
        text = _compiled_fill_text(spec, params, "wavefront", Q, R,
                                   strip=strip, tb_pack=1,
                                   live_bound=Q + R)
        out[strip] = (hlo_cost.analyze(text), hlo_cost.breakdown(text))
    return out


class TestWavefrontFill:
    def test_trip_count_extraction_exact(self, wavefront_costs):
        # live_bound = Q+R anti-diagonals, strip per scan step: the
        # compiled loop must carry known_trip_count = ceil((Q+R)/strip)
        for strip, (_, rows) in wavefront_costs.items():
            assert rows, f"strip={strip}: no loops attributed"
            trips = [r[1] for r in rows]
            assert math.ceil((Q + R) / strip) in trips, (strip, trips)

    def test_all_elementwise_no_dots(self, wavefront_costs):
        for strip, (cost, _) in wavefront_costs.items():
            assert cost.flops == 0, f"strip={strip}: DP fill has no dots"
            assert cost.ewise_flops > 0
            assert cost.bytes > 0

    def test_lane_updates_within_2x_of_cells(self, wavefront_costs):
        # the strip=1 schedule touches trips x (Q+1) diagonal lanes;
        # that count must be within 2x of the analytic Q*R cell count
        # (the slack is boundary lanes + ragged final diagonals)
        _, rows = wavefront_costs[1]
        trips = max(r[1] for r in rows)
        lane_updates = trips * (Q + 1)
        cells = Q * R
        assert cells <= lane_updates <= 2 * cells, (lane_updates, cells)

    def test_per_lane_ops_stable_across_shapes(self, linear):
        # FLOPs per lane update is a property of the recurrence, not of
        # the bucket: two shapes must agree within 2x (they agree to
        # <1% when trip extraction works; a trips=1 fallback would skew
        # the ratio by the R difference)
        spec, params = linear

        def ops_per_lane(q, r):
            text = _compiled_fill_text(spec, params, "wavefront", q, r,
                                       strip=1, tb_pack=1,
                                       live_bound=q + r)
            cost = hlo_cost.analyze(text)
            trips = max(row[1] for row in hlo_cost.breakdown(text))
            return cost.ewise_flops / (trips * (q + 1))

        a, b = ops_per_lane(64, 64), ops_per_lane(64, 128)
        assert 0.5 <= a / b <= 2.0, (a, b)


class TestMyersFill:
    @pytest.fixture(scope="class")
    def myers_cost(self):
        spec, params = kernels_zoo.make("edit_distance")
        text = _compiled_fill_text(spec, params, "myers", Q, R)
        return hlo_cost.analyze(text)

    def test_bit_parallel_ops_below_cell_count(self, myers_cost,
                                               wavefront_costs):
        # the whole point of Myers: ~17 word ops cover 32+ DP cells, so
        # the elementwise op count sits *below* the cell count — while
        # the scalar wavefront spends tens of ops per cell.  (Loop trips
        # are dynamic in r_len here, so this is the body-level count —
        # the contrast survives any trip scaling.)
        cells = Q * R
        assert 0 < myers_cost.ewise_flops < cells
        assert wavefront_costs[1][0].ewise_flops > cells

    def test_traffic_counted(self, myers_cost):
        assert myers_cost.flops == 0
        assert myers_cost.bytes > 0


class TestLoweredDialect:
    def test_lowered_fill_parses_nonzero(self, linear):
        spec, params = linear
        text = plan_mod.lower_plan_hlo(spec, params, "wavefront",
                                       (Q,), (R,), batch_size=4)
        assert "%" not in text.split("\n")[0]   # really the bare dialect
        cost = hlo_cost.analyze(text)
        assert cost.ewise_flops > 0
        assert cost.bytes > 0

    def test_analyze_plan_matches_lowered_text(self, linear):
        spec, params = linear
        kw = dict(batch_size=2, with_traceback=False, mode="fill",
                  strip=2)
        via_plan = hlo_cost.analyze_plan(spec, params, "wavefront",
                                         (Q,), (R,), **kw)
        direct = hlo_cost.analyze(
            plan_mod.lower_plan_hlo(spec, params, "wavefront",
                                    (Q,), (R,), **kw))
        assert via_plan.ewise_flops == direct.ewise_flops
        assert via_plan.bytes == direct.bytes

    def test_roofline_scales_by_analytic_trips(self, linear):
        spec, params = linear
        cost = hlo_cost.analyze_plan(spec, params, "wavefront",
                                     (Q,), (R,), batch_size=2,
                                     with_traceback=False, mode="fill")
        one = roofline.plan_roofline(cost, Q * R * 2, trips=1.0)
        two = roofline.plan_roofline(cost, Q * R * 2, trips=2.0)
        assert two.compute_s == pytest.approx(2 * one.compute_s)
        assert two.memory_s == pytest.approx(2 * one.memory_s)
        assert one.cells_per_s > two.cells_per_s

    def test_roofline_unknown_device_raises(self, linear):
        spec, params = linear
        cost = hlo_cost.analyze_plan(spec, params, "wavefront",
                                     (Q,), (R,), batch_size=2,
                                     with_traceback=False, mode="fill")
        cpu = roofline.plan_roofline(cost, Q * R * 2, backend="cpu")
        assert cpu.cells_per_s > 0
        v5e = roofline.plan_roofline(cost, Q * R * 2, backend="TPU v5 lite")
        assert v5e.bound_s < cpu.bound_s
        with pytest.raises(ValueError, match="no roofline peaks"):
            roofline.plan_roofline(cost, Q * R * 2, backend="gpu")
        with pytest.raises(ValueError, match="no roofline peaks"):
            roofline.plan_roofline(cost, Q * R * 2, backend="TPU v9")
