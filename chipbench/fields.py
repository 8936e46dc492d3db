"""Minimod's initial state and velocity model, drawn from the seed.

Every Z plane is drawn from its own key, so a jitted call can make a whole
sharded field on the devices at once and the reference can draw any block
of planes again, bit for bit, without the program's arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic import derive_rng


def velocity_profile(cfg: dict, seed: int) -> np.ndarray:
    """(nz,) c2dt2 of each Z plane: ``velocity_layers`` horizontal layers
    with boundaries and values drawn from the seed, inside
    [c2dt2_min, c2dt2_max] after the lateral modulation below."""
    nz, n = cfg["nz"], cfg["velocity_layers"]
    rng = derive_rng("velocity", seed)
    lo, hi = float(cfg["c2dt2_min"]), float(cfg["c2dt2_max"])
    # leave room for the +-5% lateral modulation
    lo_v, hi_v = lo / 0.95, hi / 1.05
    cuts = sorted(rng.randrange(1, nz) for _ in range(n - 1))
    values = [rng.uniform(lo_v, hi_v) for _ in range(n)]
    prof = np.empty(nz, np.float32)
    edges = [0] + cuts + [nz]
    for v, a, b in zip(values, edges[:-1], edges[1:]):
        prof[a:b] = v
    return prof


def planes(key, z, profile, ny: int, nx: int):
    """Planes ``z`` (a vector of global indices): (u0, u_prev0, c2dt2)."""
    def one(zi, c):
        u = jax.random.uniform(jax.random.fold_in(key, zi), (ny, nx),
                               jnp.float32, -1.0, 1.0)
        y = jnp.arange(ny, dtype=jnp.float32)[:, None] / ny
        x = jnp.arange(nx, dtype=jnp.float32)[None, :] / nx
        lateral = 1.0 + 0.05 * jnp.sin(2 * jnp.pi * y) * jnp.cos(2 * jnp.pi * x)
        return u, 0.5 * u, c * lateral
    return jax.vmap(one)(z, profile)
