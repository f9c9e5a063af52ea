"""Synthetic registration problems (paper §IV-A1) + NIREP-like brain phantoms.

Paper's scaling-study problem:
    rho_T(x)  = (sin^2 x1 + sin^2 x2 + sin^2 x3) / 3
    v*(x)     = (cos x1 sin x2, cos x2 sin x1, cos x1 sin x3)
    rho_R     = solution of the state equation (2b) with v*.

The incompressible variant uses an analytically divergence-free v*
(footnote 5: "a similar but divergence free velocity field").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import semilag
from repro.core.grid import Grid, make_grid
from repro.core.planner import make_plan
from repro.core.spectral import SpectralOps


def paper_template(grid: Grid) -> jnp.ndarray:
    x = grid.coords_jnp()
    return (jnp.sin(x[0]) ** 2 + jnp.sin(x[1]) ** 2 + jnp.sin(x[2]) ** 2) / 3.0


def paper_velocity(grid: Grid, amplitude: float = 1.0) -> jnp.ndarray:
    x = grid.coords_jnp()
    return amplitude * jnp.stack(
        [
            jnp.cos(x[0]) * jnp.sin(x[1]),
            jnp.cos(x[1]) * jnp.sin(x[0]),
            jnp.cos(x[0]) * jnp.sin(x[2]),
        ]
    )


def paper_velocity_divfree(grid: Grid, amplitude: float = 1.0) -> jnp.ndarray:
    """div v = 0 analytically: each component independent of its own coord."""
    x = grid.coords_jnp()
    return amplitude * jnp.stack(
        [jnp.sin(x[1]) * jnp.cos(x[2]), jnp.sin(x[2]) * jnp.cos(x[0]), jnp.sin(x[0]) * jnp.cos(x[1])]
    )


def synthetic_problem(n, n_t: int = 4, incompressible: bool = False, amplitude: float = 1.0,
                      ctx=None):
    """Build (rho_R, rho_T, v_star, grid) with rho_R = forward-transported rho_T.

    With a ``DistContext`` the fields are built pencil-sharded and
    transported on its mesh, so a grid too large for one chip never has to
    fit on one."""
    grid = make_grid(n) if ctx is None else ctx.grid
    rho_T = paper_template(grid)
    v_star = (
        paper_velocity_divfree(grid, amplitude) if incompressible else paper_velocity(grid, amplitude)
    )
    if ctx is None:
        ops, interp = SpectralOps(grid), None
    else:
        ops, interp = ctx.ops, ctx.interp
        rho_T, v_star = ctx.shard_scalar(rho_T), ctx.shard_vector(v_star)
    plan = make_plan(v_star, grid, ops, n_t, incompressible, interp)
    rho_R = semilag.transport_state(rho_T, plan, interp)[-1]
    return rho_R, rho_T, v_star, grid


def brain_like(n, seed: int = 0, n_blobs: int = 24, subject_jitter: float = 0.15):
    """NIREP-like multi-subject phantom pair: two 'individuals' built from the
    same anatomical blob layout with subject-specific jitter + a cortical
    shell, spectrally smoothed (stand-in for the na01/na02 MRI pair)."""
    grid = make_grid(n)
    ops = SpectralOps(grid)
    rng = np.random.default_rng(seed)
    x = np.asarray(grid.coords)

    centers = rng.uniform(np.pi * 0.4, np.pi * 1.6, (n_blobs, 3))
    widths = rng.uniform(0.15, 0.5, n_blobs)
    amps = rng.uniform(0.3, 1.0, n_blobs)

    def subject(jit_rng):
        img = np.zeros(grid.shape, np.float32)
        for c, w, a in zip(centers, widths, amps):
            cj = c + jit_rng.normal(0, subject_jitter, 3)
            d2 = sum((np.minimum(np.abs(x[i] - cj[i]), 2 * np.pi - np.abs(x[i] - cj[i]))) ** 2 for i in range(3))
            img += a * np.exp(-d2 / (2 * w**2))
        # cortical shell
        r = np.sqrt(sum((x[i] - np.pi) ** 2 for i in range(3)))
        img += 0.8 * np.exp(-((r - 1.8) ** 2) / 0.08)
        return img / img.max()

    ref = subject(np.random.default_rng(seed + 1))
    tmpl = subject(np.random.default_rng(seed + 2))
    return ops.smooth(jnp.asarray(ref)), ops.smooth(jnp.asarray(tmpl)), grid
