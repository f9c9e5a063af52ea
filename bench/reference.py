"""Plain float64 reference of the registration problem (paper §II-B, §III-B).

Written from the paper's equations and the discretization the solver states
(RK2 semi-Lagrangian transport with n_t steps, tricubic Lagrange
interpolation on the periodic grid, spectral derivatives with the Nyquist
mode zeroed for odd orders, trapezoidal time quadrature, a Gaussian
presmoothing filter of one grid cell).  It imports nothing of the program
and runs on the host CPU in float64, in slabs of rows so that it fits.

    J[v] = 1/2 ||rho(1) - rho_R||^2 + beta/2 ||Lap v||^2
    g(v) = beta Lap^2 v + b,   b = int_0^1 lam grad rho dt   (compressible)

``evaluate`` returns the terms the benchmark compares against what the
timed path produced: the reduced gradient's data term ``b`` at the returned
velocity and at ``v = 0``, the transported template ``rho(1)``, and the
range of ``det(grad y)`` of the deformation map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TWO_PI = 2.0 * np.pi


def cpu():
    return jax.devices("cpu")[0]


def _k(n):
    return np.fft.fftfreq(n, 1.0 / n)


def _k_rfft(n):
    return np.fft.rfftfreq(n, 1.0 / n)


def _kgrids(shape, nyquist_zero):
    """Broadcastable wavenumbers of an rfft spectrum (last axis halved)."""
    out = []
    for axis, n in enumerate(shape):
        k = _k_rfft(n) if axis == 2 else _k(n)
        if nyquist_zero and n % 2 == 0:
            k = np.where(np.abs(k) == n // 2, 0.0, k)
        out.append(k.reshape([-1 if a == axis else 1 for a in range(3)]))
    return out


def fft(u):
    return jnp.fft.rfftn(u, axes=(-3, -2, -1))


def ifft(s, shape):
    return jnp.fft.irfftn(s, s=shape, axes=(-3, -2, -1))


def smooth(u):
    """Gaussian filter exp(-|k h|^2 / 2), bandwidth one grid cell."""
    shape = u.shape[-3:]
    kk = _kgrids(shape, nyquist_zero=False)
    e = sum((k * (TWO_PI / n)) ** 2 for k, n in zip(kk, shape))
    return ifft(fft(u) * np.exp(-0.5 * e), shape)


def grad(u):
    """Spectral gradient of a scalar (..., N..) -> (3, ..., N..)."""
    shape = u.shape[-3:]
    s = fft(u)
    return jnp.stack([ifft(1j * k * s, shape) for k in _kgrids(shape, nyquist_zero=True)])


def div(v):
    """Spectral divergence of a vector field (3, N..) -> (N..)."""
    shape = v.shape[-3:]
    s = fft(v)
    return ifft(sum(1j * k * s[i] for i, k in enumerate(_kgrids(shape, nyquist_zero=True))),
                shape)


def lagrange(t):
    """Cubic Lagrange weights at offsets -1, 0, 1, 2 for fraction t."""
    return jnp.stack([-t * (t - 1) * (t - 2) / 6, (t + 1) * (t - 1) * (t - 2) / 2,
                      -(t + 1) * t * (t - 2) / 2, (t + 1) * t * (t - 1) / 6])


def interp(fields, disp, slab=4):
    """Evaluate each field of ``fields`` (C, N1, N2, N3) at ``x + disp``,
    ``disp`` (3, N..) in grid units, periodic, by the 64-point tricubic
    Lagrange stencil.  Rows of axis 1 are done ``slab`` at a time."""
    c = fields.shape[0]
    n1, n2, n3 = fields.shape[1:]
    flat = fields.reshape(c, -1)
    home = jnp.stack(jnp.meshgrid(jnp.arange(slab), jnp.arange(n2), jnp.arange(n3),
                                  indexing="ij")).astype(disp.dtype)
    offs = jnp.arange(-1, 3)

    def rows(i):
        d = jax.lax.dynamic_slice_in_dim(disp, i * slab, slab, axis=1)
        x = home + d + jnp.asarray([i * slab, 0, 0], disp.dtype).reshape(3, 1, 1, 1)
        base = jnp.floor(x)
        w = lagrange(x - base)  # (4, 3, slab, N2, N3)
        b = base.astype(jnp.int32)
        i1 = jnp.mod(b[0][None] + offs.reshape(4, 1, 1, 1), n1)
        i2 = jnp.mod(b[1][None] + offs.reshape(4, 1, 1, 1), n2)
        i3 = jnp.mod(b[2][None] + offs.reshape(4, 1, 1, 1), n3)
        idx = (i1[:, None, None] * (n2 * n3) + i2[None, :, None] * n3 + i3[None, None, :])
        wt = w[:, 0][:, None, None] * w[:, 1][None, :, None] * w[:, 2][None, None, :]
        return jnp.stack([jnp.sum(flat[ci][idx] * wt, axis=(0, 1, 2)) for ci in range(c)])

    out = jax.lax.map(rows, jnp.arange(n1 // slab))  # (n1/slab, C, slab, N2, N3)
    return jnp.moveaxis(out, 1, 0).reshape(fields.shape)


def departure(v, dt):
    """RK2 departure displacement in grid units for velocity ``v``:
    X* = x - dt v(x),  X = x - dt/2 (v(x) + v(X*))."""
    h = jnp.asarray([TWO_PI / n for n in v.shape[-3:]], v.dtype).reshape(3, 1, 1, 1)
    vg = v / h
    return -0.5 * dt * (vg + interp(vg, -dt * vg))


def state(rho0, d, n_t):
    """rho_t + v . grad rho = 0: the slices rho(t_k), k = 0..n_t."""
    series = [rho0]
    for _ in range(n_t):
        series.append(interp(series[-1][None], d)[0])
    return jnp.stack(series)


def adjoint(lam1, d_adj, divv, n_t):
    """-lam_t - div(v lam) = 0 backward from lam(1): slices in t order."""
    dt = 1.0 / n_t
    series = [lam1]
    for _ in range(n_t):
        lam = series[-1]
        lam0x, f0x = interp(jnp.stack([lam, lam * divv]), d_adj)
        lam_star = lam0x + dt * f0x
        series.append(lam0x + 0.5 * dt * (f0x + lam_star * divv))
    return jnp.stack(series[::-1])


def deformation(v, d, n_t):
    """u(1) of u_t + v . grad u = -v, u(0) = 0 (y = x + u), physical units."""
    dt = 1.0 / n_t
    f0x = interp(-v, d)
    u = jnp.zeros_like(v)
    for _ in range(n_t):
        u = interp(u, d) + 0.5 * dt * (f0x - v)
    return u


def jacobian_det(u):
    a = jnp.swapaxes(grad(u), 0, 1) + jnp.eye(3)[:, :, None, None, None]
    return (a[0, 0] * (a[1, 1] * a[2, 2] - a[1, 2] * a[2, 1])
            - a[0, 1] * (a[1, 0] * a[2, 2] - a[1, 2] * a[2, 0])
            + a[0, 2] * (a[1, 0] * a[2, 1] - a[1, 1] * a[2, 0]))


def data_term(v, rho_r, rho_t, n_t):
    """b = int lam grad rho dt (trapezoid) and rho(1), compressible."""
    dt = 1.0 / n_t
    rho = state(rho_t, departure(v, dt), n_t)
    lam = adjoint(rho_r - rho[-1], departure(-v, dt), div(v), n_t)
    w = np.full(n_t + 1, dt)
    w[0] = w[-1] = 0.5 * dt
    b = sum(w[k] * lam[k] * grad(rho[k]) for k in range(n_t + 1))
    return b, rho[-1]


@functools.partial(jax.jit, static_argnames=("n_t", "presmooth"))
def _evaluate(v, rho_r, rho_t, *, n_t, presmooth):
    if presmooth:
        rho_r, rho_t = smooth(rho_r), smooth(rho_t)
    b, rho1 = data_term(v, rho_r, rho_t, n_t)
    # at v = 0 every transport is the identity: b(0) = (rho_R - rho_T) grad rho_T
    b0 = (rho_r - rho_t) * grad(rho_t)
    det = jacobian_det(deformation(v, departure(v, 1.0 / n_t), n_t))
    return {"b": b, "b0": b0, "rho1": rho1, "rho_r": rho_r, "rho_t": rho_t,
            "det_min": jnp.min(det), "det_max": jnp.max(det)}


def evaluate(v, rho_r, rho_t, n_t, presmooth):
    """The reference terms at ``v`` (3, N..) for the pair as the solver got
    it (``presmooth``: filtered first, as ``register`` does).  numpy in,
    numpy float64 out."""
    with jax.enable_x64(True):
        args = jax.device_put([np.asarray(a, np.float64) for a in (v, rho_r, rho_t)], cpu())
        out = _evaluate(*args, n_t=n_t, presmooth=presmooth)
        return {k: np.asarray(a) for k, a in out.items()}


def carried_image_error(v, lap2):
    """|Lap^-2 lap2 - (v - mean v)| / |v - mean v|, in float64 on the host:
    the inverse damps the f32 roundoff of both by |k|^-4, so it checks the
    solver's carried Lap^2 v against v itself."""
    v = np.asarray(v, np.float64)
    lap2 = np.asarray(lap2, np.float64)
    n = v.shape[-3:]
    k2 = sum(np.meshgrid(*(_k(m) ** 2 for m in n), indexing="ij"))
    inv = np.divide(1.0, k2**2, out=np.zeros_like(k2), where=k2 > 0)
    back = np.fft.ifftn(np.fft.fftn(lap2, axes=(-3, -2, -1)) * inv, axes=(-3, -2, -1)).real
    v0 = v - v.mean(axis=(-3, -2, -1), keepdims=True)
    with np.errstate(invalid="ignore", divide="ignore"):  # v = 0 reads NaN: a failure
        return float(np.linalg.norm(back - v0) / np.linalg.norm(v0))
