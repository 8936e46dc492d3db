"""Thin names over the installed JAX (0.9) used across the codebase.

Every site in src/tests/examples/benchmarks imports these from here:

* :func:`shard_map`, :func:`typeof`, :func:`pcast`, :func:`axis_size` —
  ``jax.shard_map`` (vma checking on), ``jax.typeof``, ``lax.pcast`` and
  ``lax.axis_size`` themselves;
* :func:`all_gather_invariant` — the Varying->Invariant all-gather, which
  JAX keeps internal;
* :func:`make_mesh` — ``jax.make_mesh`` with every axis ``Auto`` unless
  told otherwise.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax import lax
from jax._src.lax.parallel import all_gather_invariant

__all__ = [
    "shard_map",
    "typeof",
    "pcast",
    "axis_size",
    "all_gather_invariant",
    "make_mesh",
]

shard_map = jax.shard_map
typeof = jax.typeof
pcast = lax.pcast
axis_size = lax.axis_size


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, axis_types: Optional[Sequence] = None, devices=None):
    """``jax.make_mesh`` whose ``axis_types`` default to all ``Auto``.

    ``axis_types`` may be a tuple of ``jax.sharding.AxisType``, the string
    ``"auto"``, or None (also all ``Auto``).
    """
    if axis_types is None or axis_types == "auto":
        axis_types = (jax.sharding.AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=tuple(axis_types), devices=devices)
