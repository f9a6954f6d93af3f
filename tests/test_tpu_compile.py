"""Compile the main-path plans for a described TPU v5e, with no chip.

The TPU compiler is installed with jax and compiles for a topology that
is described, not attached: a block layout the chip refuses, VMEM
overuse or a program that does not fit the device fails here, at no chip
time.  Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture (never at import
time): only one process at a time may load the TPU library, and every
test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import kernels_zoo
from repro.runtime import plan as plan_mod


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _plan(kernel, engine, bucket, batch, *, mesh=None, tb=True, **opts):
    spec, params = kernels_zoo.make(kernel)
    o = plan_mod.resolve_engine_options(spec, engine, opts)
    wtb = spec.traceback is not None and tb
    key = plan_mod.PlanKey(
        kernel=spec.name, engine=engine, bucket_shape=((bucket,), (bucket,)),
        batch_size=batch, with_traceback=wtb, strip=o["strip"],
        tb_pack=o["tb_pack"], semiring=spec.semiring.name, xdrop=o["xdrop"])
    return spec, params, plan_mod.CompiledPlan(key, spec, engine, mesh=mesh)


def _compile(plan, spec, params, bucket, batch, arg_sharding, par_sharding):
    cdt = jnp.dtype(spec.char_dtype)
    S = jax.ShapeDtypeStruct
    p = jax.tree.map(lambda x: S(jnp.shape(x), jnp.result_type(x),
                                 sharding=par_sharding), params)
    args = (S((batch, bucket), cdt, sharding=arg_sharding),
            S((batch, bucket), cdt, sharding=arg_sharding),
            S((batch,), jnp.int32, sharding=arg_sharding),
            S((batch,), jnp.int32, sharding=arg_sharding))
    return plan._fn.lower(p, *args).compile()


def test_wavefront_global_affine_1024_compiles(one_chip):
    spec, params, plan = _plan("global_affine", "wavefront", 1024, 64,
                               strip=8)
    compiled = _compile(plan, spec, params, 1024, 64, one_chip, one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


@pytest.mark.parametrize("bucket", [256, 4096])
def test_pallas_plan_compiles_to_tpu_kernel(one_chip, bucket):
    spec, params, plan = _plan("global_affine", "pallas", bucket, 8)
    compiled = _compile(plan, spec, params, bucket, 8, one_chip, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_myers_pallas_512_compiles_to_tpu_kernel(one_chip):
    spec, params, plan = _plan("edit_distance", "myers_pallas", 512, 16,
                               tb=False)
    compiled = _compile(plan, spec, params, 512, 16, one_chip, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_service_plan_compiles_on_four_devices(topo):
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    spec, params, plan = _plan("global_affine", "wavefront", 256, 64,
                               mesh=mesh, strip=8)
    compiled = _compile(plan, spec, params, 256, 64,
                        NamedSharding(mesh, P("data")),
                        NamedSharding(mesh, P()))
    assert len(compiled.input_shardings[0][1].device_set) == 4
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert 0 < per_device


def test_service_plan_lowers_without_unusable_donation(one_chip):
    """A plan must not donate inputs that no output can reuse: XLA warns
    and keeps the buffers (the padded uint8 sequences match no output)."""
    import warnings
    spec, params, plan = _plan("global_affine", "wavefront", 256, 8, strip=8)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _compile(plan, spec, params, 256, 8, one_chip, one_chip)
    assert not [w for w in caught if "donated" in str(w.message)]


def _op_name_parts(text):
    import re
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


def _long_read_plan(bucket):
    """The served plan of a long-read deployment (local affine, a 2 GiB
    traceback budget) at one bucket, as the chip compiles it."""
    from repro.serve import AlignmentService
    svc = AlignmentService(max_len=16384, tb_budget_bytes=2 << 30)
    rows = svc.block_for("local_affine", (bucket, bucket))
    return _plan("local_affine", "wavefront", bucket, rows, strip=8), rows


@pytest.mark.parametrize("bucket", [1024, 2048, 4096, 8192, 16384])
def test_long_read_plans_name_their_phases(one_chip, bucket):
    (spec, params, plan), rows = _long_read_plan(bucket)
    text = _compile(plan, spec, params, bucket, rows, one_chip,
                    one_chip).as_text()
    assert {plan_mod.FILL_SCOPE, plan_mod.TRACEBACK_SCOPE} <= \
        _op_name_parts(text)


def test_phase_scopes_leave_the_chips_instruction_names(one_chip,
                                                        monkeypatch):
    """A trace names device ops by instruction: the scopes must leave
    every instruction of the compiled program as it was."""
    import contextlib
    import re

    def names(text):
        return re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) =", text, re.M)

    (spec, params, plan), rows = _long_read_plan(16384)
    scoped = _compile(plan, spec, params, 16384, rows, one_chip, one_chip)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    (_, _, plain_plan), _ = _long_read_plan(16384)
    plain = _compile(plain_plan, spec, params, 16384, rows, one_chip,
                     one_chip)
    assert names(plain.as_text()) == names(scoped.as_text())
    assert plan_mod.FILL_SCOPE not in _op_name_parts(plain.as_text())
