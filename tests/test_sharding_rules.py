"""Logical-axis resolution and HLO cost parser units (no devices needed)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding import TRAIN_RULES, INFER_RULES, resolve_spec


class FakeMesh:
    """Just enough of a Mesh for resolve_spec (shape lookup)."""
    def __init__(self, **shape):
        self.shape = shape


def test_basic_resolution():
    mesh = FakeMesh(data=16, model=16)
    spec = resolve_spec((100352, 5120), ("vocab", "embed"),
                        TRAIN_RULES, mesh)
    assert spec == P("model", "data")


def test_divisibility_fallback():
    mesh = FakeMesh(data=16, model=16)
    # 10 kv heads don't divide 16 -> replicate that dim
    spec = resolve_spec((5120, 10, 128), ("embed", "kv_heads", "head_dim"),
                        TRAIN_RULES, mesh)
    assert spec == P("data", None, None)


def test_used_axis_not_reused():
    mesh = FakeMesh(data=16, model=16)
    # batch grabs data; embed's candidate (data) is taken -> replicated
    spec = resolve_spec((256, 4096, 5120), ("batch", None, "embed"),
                        TRAIN_RULES, mesh)
    assert spec == P("data", None, None)


def test_multi_pod_batch():
    mesh = FakeMesh(pod=2, data=16, model=16)
    spec = resolve_spec((256, 4096), ("batch", None), TRAIN_RULES, mesh)
    assert spec == P(("pod", "data"), None)
    # batch=1 (long_500k): nothing divides -> replicate
    spec = resolve_spec((1, 4096), ("batch", None), TRAIN_RULES, mesh)
    assert spec == P(None, None)


def test_cache_seq_fallback_for_small_kv():
    mesh = FakeMesh(data=16, model=16)
    # kv=8 < 16: kv falls back, cache_seq picks up the model axis (decode)
    spec = resolve_spec((128, 32768, 8, 128),
                        ("batch", "cache_seq", "kv_heads", "head_dim"),
                        INFER_RULES, mesh)
    assert spec == P("data", "model", None, None)


# ---------------------------------------------------------------------------
# HLO cost parser
# ---------------------------------------------------------------------------
def test_hlo_cost_scan_trip_counts():
    from repro.launch import hlo_cost

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    comp = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
                            jax.ShapeDtypeStruct((64, 64), jnp.float32)
                            ).compile()
    c = hlo_cost.analyze(comp.as_text())
    assert c.flops == 7 * 2 * 64 ** 3
    # XLA's own analysis undercounts (documents why we parse ourselves)
    assert (comp.cost_analysis() or {}).get("flops", 0) < c.flops / 2


def test_hlo_cost_nested_scan():
    from repro.launch import hlo_cost

    def g(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    comp = jax.jit(g).lower(jax.ShapeDtypeStruct((32, 32), jnp.float32),
                            jax.ShapeDtypeStruct((32, 32), jnp.float32)
                            ).compile()
    assert hlo_cost.analyze(comp.as_text()).flops == 15 * 2 * 32 ** 3


def test_hlo_cost_grad_flops():
    from repro.launch import hlo_cost

    def f(x, w):
        return jnp.sum(jnp.tanh(x @ w))

    g = jax.grad(f, argnums=1)
    comp = jax.jit(g).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
                            jax.ShapeDtypeStruct((64, 64), jnp.float32)
                            ).compile()
    flops = hlo_cost.analyze(comp.as_text()).flops
    assert flops >= 2 * 2 * 64 ** 3          # fwd dot + bwd dot at least


def test_wire_bytes_model():
    from repro.launch.roofline import wire_bytes
    recs = [("all-reduce", 1000, 4, 1.0), ("all-gather", 1000, 4, 2.0),
            ("collective-permute", 1000, 2, 1.0),
            ("all-reduce", 1000, 1, 5.0)]   # group 1 -> free
    got = wire_bytes(recs)
    assert got == pytest.approx(2 * 750 + 2 * 750 + 1000)
