"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests must see the
host's single device; multi-device tests spawn subprocesses."""
from __future__ import annotations

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_kernel_inputs(rng, spec, nq: int, nr: int):
    """Random (query, ref) matching a kernel spec's alphabet."""
    import jax.numpy as jnp
    if spec.char_shape == (5,):          # profile
        from repro.core.kernels_zoo.profile import make_profile
        return (jnp.asarray(make_profile(rng, nq)),
                jnp.asarray(make_profile(rng, nr)))
    if spec.char_shape == (2,):          # complex DTW signal
        return (jnp.asarray(rng.normal(size=(nq, 2)).astype(np.float32)),
                jnp.asarray(rng.normal(size=(nr, 2)).astype(np.float32)))
    if spec.char_dtype == jnp.int32:     # sDTW squiggle
        return (jnp.asarray(rng.integers(0, 128, nq).astype(np.int32)),
                jnp.asarray(rng.integers(0, 128, nr).astype(np.int32)))
    if spec.name == "protein_local":
        return (jnp.asarray(rng.integers(0, 20, nq).astype(np.uint8)),
                jnp.asarray(rng.integers(0, 20, nr).astype(np.uint8)))
    return (jnp.asarray(rng.integers(0, 4, nq).astype(np.uint8)),
            jnp.asarray(rng.integers(0, 4, nr).astype(np.uint8)))


def seed_plan(spec, engine: str, bucket: int):
    """The seed executable the plan cache's defaults must match bit for
    bit: vmapped fill plus per-row while-loop traceback at strip=1,
    tb_pack=1, the fill walking every diagonal of the bucket (no early
    exit)."""
    import functools
    import jax
    from repro.runtime import plan as plan_mod, registry
    supported = registry.engine_options(engine)
    opts = {k: 1 for k in ("strip", "tb_pack") if k in supported}
    if supported.get("live_bound") == "dynamic":
        opts["live_bound"] = 2 * bucket
    engine_fn = functools.partial(registry.get_engine(engine), **opts)
    single = functools.partial(plan_mod.align_impl, spec, engine_fn)
    return jax.jit(jax.vmap(single, in_axes=(None, 0, 0, 0, 0)))
