"""Pallas grouped expert-MLP kernel — the compute core of the fused dispatch.

One grid step per local expert: the expert's landed rows (all sources,
padded to the plan's ``cap_pad``) run through the silu-gated MLP with f32
accumulation on the MXU.  The fused TPU dispatch kernel inlines the same
loop between its remote copies; this standalone entry point exists so the
compute core is testable in the Pallas interpreter against
:func:`repro.kernels.moe_dispatch.ref.expert_mlp_ref` without any
collective machinery.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vma import out_struct
from repro.kernels.plan import resolve_interpret

__all__ = ["expert_mlp_pallas"]


def _expert_mlp_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    x = x_ref[0]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)


def expert_mlp_pallas(x, wg, wu, wd, *, interpret: Optional[bool] = None):
    """``x (E, C, d)``, ``wg/wu (E, d, f)``, ``wd (E, f, d)`` -> ``(E, C, d)``.

    Grid over experts; each step holds one expert's rows and weights in
    VMEM.  ``interpret=None`` resolves from the backend at call time.
    """
    E, C, d = x.shape
    f = wg.shape[2]
    return pl.pallas_call(
        _expert_mlp_kernel,
        grid=(E,),
        in_specs=[
            pl.BlockSpec((1, C, d), lambda e: (e, 0, 0)),
            pl.BlockSpec((1, d, f), lambda e: (e, 0, 0)),
            pl.BlockSpec((1, d, f), lambda e: (e, 0, 0)),
            pl.BlockSpec((1, f, d), lambda e: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, d), lambda e: (e, 0, 0)),
        out_shape=out_struct((E, C, d), x.dtype, x, wg, wu, wd),
        interpret=resolve_interpret(interpret),
    )(x, wg, wu, wd)
