"""Plain Minimod oracle, computed in blocks of Z planes.

A copy of the program's ``wave_step_ref`` (8th-order 25-point star, zero
halo at the grid's faces, leapfrog in time), so that the yardstick does not
move with the program.  A whole 1024^3 field set does not fit one chip, so
the field after K steps is computed block by block: output planes
[z0, z1) need the initial planes [z0 - K·R, z1 + K·R), which are drawn
again from the seed (``chipbench.fields``); the zeros padded at a block's
inner edge reach K·R planes inward in K steps and are cut away.

``dtype=bfloat16`` is the control: the same oracle in the next precision
below the configuration's float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import fields

COEFFS = (-205.0 / 72.0, 8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0)
RADIUS = 4


def laplacian(u, dx: float = 1.0):
    up = jnp.pad(u, RADIUS)
    z, y, x = u.shape
    c0, *cs = COEFFS
    lap = 3.0 * c0 * u
    for r, c in zip(range(1, RADIUS + 1), cs):
        for axis in range(3):
            lo = [slice(RADIUS, RADIUS + z), slice(RADIUS, RADIUS + y),
                  slice(RADIUS, RADIUS + x)]
            hi = list(lo)
            lo[axis] = slice(RADIUS - r, RADIUS - r + u.shape[axis])
            hi[axis] = slice(RADIUS + r, RADIUS + r + u.shape[axis])
            lap = lap + c * (up[tuple(lo)] + up[tuple(hi)])
    return lap / (dx * dx)


def wave_step(u, u_prev, c2dt2, dx: float = 1.0):
    return (2.0 * u - u_prev + c2dt2 * laplacian(u, dx)).astype(u.dtype)


class BlockedOracle:
    """u after K steps from the seeded state, one block of planes at a time."""

    def __init__(self, cfg: dict, steps: int, block: int,
                 dtype=jnp.float32):
        self.cfg, self.K, self.block, self.dtype = cfg, steps, block, dtype
        self.halo = steps * RADIUS
        dx = float(cfg["dx"])

        def run(seed, z, profile):
            u, up, c2 = fields.planes(jax.random.PRNGKey(seed), z, profile,
                                      cfg["ny"], cfg["nx"])
            # planes outside the grid are the Dirichlet zeros
            inside = ((z >= 0) & (z < cfg["nz"]))[:, None, None]
            u = jnp.where(inside, u, 0.0).astype(dtype)
            up = jnp.where(inside, up, 0.0).astype(dtype)
            c2 = c2.astype(dtype)

            def body(carry, _):
                u, up = carry
                un = wave_step(u, up, c2, dx)
                return (jnp.where(inside, un, 0).astype(dtype), u), None

            (u, _), _ = lax.scan(body, (u, up), None, length=steps)
            return u.astype(jnp.float32)

        self._run = jax.jit(run)

    def blocks(self):
        nz, b = self.cfg["nz"], self.block
        return [(z0, min(z0 + b, nz)) for z0 in range(0, nz, b)]

    def block_field(self, seed32: int, profile, z0: int, z1: int,
                    device=None):
        """(z1 - z0, ny, nx) float32: planes [z0, z1) after K steps."""
        h = self.halo
        z = jnp.arange(z0 - h, z0 + self.block + h, dtype=jnp.int32)
        prof = jnp.asarray(profile)[jnp.clip(z, 0, self.cfg["nz"] - 1)]
        seed = jnp.asarray(seed32, jnp.uint32)
        if device is not None:
            z, prof, seed = jax.device_put((z, prof, seed), device)
        out = self._run(seed, z, prof)
        return out[h:h + (z1 - z0)]
