"""Mesh construction with the sharding mode the code base assumes.

``jax.make_mesh`` is called with Auto axes throughout: every sharded
program here relies on GSPMD propagation, not explicit-sharding types.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, **kw):
    """``jax.make_mesh`` with Auto axes unless ``axis_types`` is given."""
    kw.setdefault("axis_types",
                  (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, **kw)
