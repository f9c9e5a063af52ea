"""Traffic generator: the window's registration inputs from ``--seed``.

One generator serves every mix.  A mix file (``bench/traffic/<mix>.json``)
gives the batch (registrations admitted together; 1 = one in flight, back
to back); a configuration gives the grid and the amplitudes of the paper's
synthetic velocity (arXiv:1608.03630 §IV-A1):

    rho_T(x) = (sin^2 x1 + sin^2 x2 + sin^2 x3) / 3
    v*(x)    = a (cos x1 sin x2, cos x2 sin x1, cos x1 sin x3)
    rho_R    = rho(1) of rho_t + v* . grad rho = 0, rho(0) = rho_T

``rho_R(x) = rho_T(X(x))`` where ``X`` follows the characteristic back from
``x`` (dX/ds = -v*(X), s in [0, 1]); it is integrated pointwise with RK4,
so the pair is made on the device in one jitted call, with no interpolation.
A configuration with ``presmooth_inputs`` hands the solver the pair filtered
by a Gaussian of one grid cell (what ``register()`` does to its inputs).
Registration ``(i, j)`` (batch ``i``, subject ``j``) gets subject ``j``'s
amplitude and a whole-voxel periodic roll of the pair drawn from
``(seed, i, j)``: the roll is exact on the periodic grid, so every input is
different and every one poses the same problem with the same work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

RK4_STEPS = 32


def template(x):
    return (jnp.sin(x[0]) ** 2 + jnp.sin(x[1]) ** 2 + jnp.sin(x[2]) ** 2) / 3.0


def velocity(x, a):
    return a * jnp.stack([jnp.cos(x[0]) * jnp.sin(x[1]), jnp.cos(x[1]) * jnp.sin(x[0]),
                          jnp.cos(x[0]) * jnp.sin(x[2])])


def coords(shape, dtype=jnp.float32):
    axes = [jnp.arange(n, dtype=dtype) * (2 * np.pi / n) for n in shape]
    return jnp.stack(jnp.meshgrid(*axes, indexing="ij"))


def smooth(u):
    """Gaussian filter exp(-|k h|^2 / 2) of one grid cell over the last three
    axes (the presmoothing of the paper's pipeline, sec. III-B1)."""
    shape = u.shape[-3:]
    e = 0.0
    for axis, n in enumerate(shape):
        k = (np.fft.rfftfreq(n, 1.0 / n) if axis == 2 else np.fft.fftfreq(n, 1.0 / n))
        e = e + ((k * 2 * np.pi / n) ** 2).reshape([-1 if a == axis else 1 for a in range(3)])
    spec = jnp.fft.rfftn(u, axes=(-3, -2, -1)) * jnp.asarray(np.exp(-0.5 * e), jnp.float32)
    return jnp.fft.irfftn(spec, s=shape, axes=(-3, -2, -1)).astype(u.dtype)


@functools.partial(jax.jit, static_argnames=("shape", "presmooth"))
def pairs(amplitudes, shape, presmooth=False):
    """``rho_R`` for each amplitude ``(A, N..)`` and the template ``rho_T``,
    filtered by ``smooth`` when ``presmooth``."""
    x = coords(shape)
    h = -1.0 / RK4_STEPS

    def back(a):
        def rk4(_, y):
            k1 = velocity(y, a)
            k2 = velocity(y + 0.5 * h * k1, a)
            k3 = velocity(y + 0.5 * h * k2, a)
            k4 = velocity(y + h * k3, a)
            return y + h / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4)

        return template(jax.lax.fori_loop(0, RK4_STEPS, rk4, x))

    rho_r, rho_t = jax.lax.map(back, amplitudes), template(x)
    return (smooth(rho_r), smooth(rho_t)) if presmooth else (rho_r, rho_t)


@jax.jit
def roll(x, shift):
    """Periodic roll of the three grid axes by a traced shift (one program
    for every shift)."""
    for axis in range(3):
        x = jnp.roll(x, shift[axis], axis=axis)
    return x


class Traffic:
    """The inputs of one run: ``batch(i)`` is the list of ``(rho_R, rho_T)``
    pairs of batch ``i``; ``shift(i, j)`` its rolls."""

    def __init__(self, cfg: dict, mix: dict, seed: int, place=lambda x: x):
        self.shape = tuple(cfg["grid"])
        self.amplitudes = [float(a) for a in cfg["amplitudes"]]
        self.batch_size = int(mix["batch"])
        self.seed = int(seed)
        rho_r, rho_t = pairs(jnp.asarray(self.amplitudes, jnp.float32), self.shape,
                             presmooth=bool(cfg.get("presmooth_inputs", False)))
        self.rho_r = [place(r) for r in rho_r]
        self.rho_t = place(rho_t)

    def shift(self, i: int, j: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, i, j])
        return rng.integers(0, self.shape, size=3).astype(np.int32)

    def pair(self, i: int, j: int):
        s = jnp.asarray(self.shift(i, j))
        return roll(self.rho_r[j % len(self.amplitudes)], s), roll(self.rho_t, s)

    def batch(self, i: int):
        return [self.pair(i, j) for j in range(self.batch_size)]
