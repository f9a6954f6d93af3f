"""Single-pair engines whose ``vmap`` is the kernel's own batch axis.

The plan cache runs a block of pairs as ``vmap(engine)``.  A vmapped
``pallas_call`` becomes a grid over the batch with the kernel's blocks
unchanged, and the TPU compiler refuses those blocks (a per-pair SMEM
length pair, say, becomes a ``(squeezed, 2)`` block of a ``(B, 2)``
array).  The Pallas kernels are therefore written batched, and
:func:`grid_vmap` turns such a batched launch into a single-pair function
whose batching rule calls the launch once on the whole block.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def grid_vmap(launch: Callable) -> Callable:
    """``launch(shared, *arrays)`` takes arrays with a leading batch axis
    and returns batched outputs.  The result ``f(shared, *arrays)`` takes
    one pair; ``vmap(f)`` is one ``launch`` on the block.  ``shared`` (the
    kernel's parameters) must not be batched."""

    @jax.custom_batching.custom_vmap
    def f(shared, *arrays):
        out = launch(shared, *(a[None] for a in arrays))
        return jax.tree.map(lambda x: x[0], out)

    @f.def_vmap
    def _rule(axis_size, in_batched, shared, *arrays):
        if any(jax.tree.leaves(in_batched[0])):
            raise NotImplementedError(
                "a Pallas engine shares one parameter set across a batch")
        whole = [a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
                 for a, batched in zip(arrays, in_batched[1:])]
        out = launch(shared, *whole)
        return out, jax.tree.map(lambda _: True, out)

    return f
