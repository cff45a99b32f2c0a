"""shard_map typing helper shared by the Pallas kernels and the models.

Under ``jax.shard_map`` (with its default ``check_vma=True``) every
value carries the set of manual mesh axes it varies over (its *vma*).
A ``pallas_call`` cannot infer that set for its outputs, so each kernel
states it on its ``out_shape`` with :func:`vma_of` of its inputs.
"""
from __future__ import annotations

import jax


def vma_of(*xs) -> frozenset:
    """Union of the varying manual axes of ``xs`` (empty outside
    ``shard_map``)."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))
