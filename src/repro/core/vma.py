"""Varying-manual-axes helpers for code shared between shard_map and plain jit.

Under ``shard_map`` with vma checking (the default, and the thing that makes
AD through our explicit collectives sound), freshly created constants are
*unvarying* while values derived from inputs are *varying*; loop carries must
match.  ``zeros_like_varying`` creates a zero array that inherits the varying
axes of a reference value, working identically (and at ~zero cost) outside
shard_map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["zeros_varying", "full_varying", "out_struct"]


def zeros_varying(shape, dtype, like):
    """Zeros of ``shape``/``dtype`` carrying ``like``'s varying axes."""
    tag = (like.reshape(-1)[0] * 0).astype(dtype)
    return jnp.zeros(shape, dtype) + tag


def full_varying(shape, dtype, value, like):
    tag = (like.reshape(-1)[0] * 0).astype(dtype)
    return jnp.full(shape, value, dtype) + tag


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A kernel's output shape, varying over every axis its operands vary
    over: ``pallas_call`` under a vma-checked ``shard_map`` needs the
    output's vma spelled out (outside shard_map it is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
