"""Spectral restriction/prolongation between periodic grids.

On the paper's spectral discretization, grid transfer is *exact* Fourier
mode selection: restriction truncates the fine spectrum to the coarse
grid's modes, prolongation zero-pads the coarse spectrum into the fine
layout.  With the grids' cell-volume-weighted inner products the two are
exact adjoints of each other, and ``restrict(prolong(g)) == g`` for every
coarse field with zero Nyquist content (both operators symmetrically drop
the coarse Nyquist plane, whose fine counterpart ±M/2 is ambiguous).

The operators are generic over the ``SpectralOps`` FFT backend: with two
``LocalFFT`` backends they are rfft truncation on one device; with two
``PencilFFT`` backends (``DistContext.ops``) the truncation happens on the
k-space pencils right after the forward transform and the coarse inverse
transform re-shards onto the coarse context's mesh layout — no gather of
the fine field ever materializes.

Because a coarse mode set is two *contiguous* runs per axis (positive
head, negative tail — see ``spectral.mode_indices``), both directions are
expressed as slices + concatenation rather than gather/scatter.  That is
not just cosmetic: GSPMD lowers the slice/concat zero-pad to the sharded
all-to-all re-distribution on every mesh layout, where the old
``.at[idx].set`` scatter all-gathered the whole coarse spectrum per chip
on folded multi-pod pencil axes (74 MB/chip at 256^3 on 2x16x16 —
EXPERIMENTS §Dry-run; pinned by ``tests/test_coalesce.py``).  The padded
result additionally carries the backend's k-space sharding hint
(``PencilFFT.constrain_k``) so the propagation pass cannot fall back to
replication.

The spectrum-level halves (``restrict_spec`` / ``pad_spec``) are exposed
for callers that already hold a spectrum: the V-cycle preconditioner
(``multilevel/precond.py``) splits a residual into coarse + high-mode
parts and reassembles the correction with ONE fine forward and ONE fine
inverse per application instead of four.

Normalization: ``restrict`` samples the band-limited interpolant on the
coarse grid (exact on resolved modes), ``prolong`` is exact band-limited
interpolation (a grid function round-trips bit-for-bit through
``restrict(prolong(.))``).  Leading batch axes (vector components, time
series) pass straight through both backends.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro import telemetry
from repro.core.spectral import LocalFFT, SpectralOps, nyquist_mask


def _layout(ops: SpectralOps) -> bool:
    """True when the backend stores an rfft (half-spectrum) last axis."""
    return isinstance(ops.fft, LocalFFT)


def _check_pair(fine_ops: SpectralOps, coarse_ops: SpectralOps) -> bool:
    if _layout(fine_ops) != _layout(coarse_ops):
        raise ValueError(
            "transfer requires matching spectrum layouts (both LocalFFT or both "
            f"pencil backends); got {type(fine_ops.fft).__name__} -> "
            f"{type(coarse_ops.fft).__name__}"
        )
    return _layout(fine_ops)


def _mask(fine_ops: SpectralOps, coarse_ops: SpectralOps, rfft: bool) -> jnp.ndarray:
    fine, coarse = fine_ops.grid.shape, coarse_ops.grid.shape
    m1, m2, m3 = (nyquist_mask(fine[a], coarse[a], rfft=(rfft and a == 2)) for a in range(3))
    return jnp.asarray(m1[:, None, None] * m2[None, :, None] * m3[None, None, :])


def _head_tail(n_fine: int, n_coarse: int, rfft: bool) -> tuple[int, int]:
    """Lengths of the two contiguous mode runs of a coarse axis inside a
    fine axis (positive head, negative tail; tail = 0 for rfft axes)."""
    if rfft:
        return n_coarse // 2 + 1, 0
    return n_coarse - n_coarse // 2, n_coarse // 2


def _zero_pad(x, axis: int, lo: int, hi: int):
    """lax.pad with zeros on one axis — the one spectrum-surgery primitive
    the SPMD partitioner handles shard-locally (a concatenate or scatter
    along a sharded dimension makes GSPMD replicate the operand first:
    the all-gather/all-reduce pathologies this module exists to avoid)."""
    cfg = [(0, 0, 0)] * x.ndim
    cfg[axis % x.ndim] = (lo, hi, 0)
    return lax.pad(x, jnp.zeros((), x.dtype), cfg)


def _truncate_axis(spec, axis: int, n_fine: int, n_coarse: int, rfft: bool):
    if n_coarse == n_fine:
        return spec
    n_pos, n_neg = _head_tail(n_fine, n_coarse, rfft)
    head = lax.slice_in_dim(spec, 0, n_pos, axis=axis)
    if n_neg == 0:
        return head
    tail = lax.slice_in_dim(spec, n_fine - n_neg, n_fine, axis=axis)
    # [head | tail] via two shard-local zero-pads + add (no concat)
    return _zero_pad(head, axis, 0, n_neg) + _zero_pad(tail, axis, n_pos, 0)


def _pad_axis(spec, axis: int, n_fine: int, n_coarse: int, rfft: bool):
    if n_coarse == n_fine:
        return spec
    n_pos, n_neg = _head_tail(n_fine, n_coarse, rfft)
    size_f = n_fine // 2 + 1 if rfft else n_fine
    head = lax.slice_in_dim(spec, 0, n_pos, axis=axis)
    out = _zero_pad(head, axis, 0, size_f - n_pos)
    if n_neg:
        tail = lax.slice_in_dim(spec, n_pos, n_pos + n_neg, axis=axis)
        out = out + _zero_pad(tail, axis, size_f - n_neg, 0)
    return out


def restrict_spec(
    spec: jnp.ndarray, fine_ops: SpectralOps, coarse_ops: SpectralOps
) -> jnp.ndarray:
    """Truncate a fine-layout spectrum to the coarse layout (mask + the
    restriction normalization applied): ``restrict = coarse.inv o this o
    fine.fwd``."""
    rfft = _check_pair(fine_ops, coarse_ops)
    fine, coarse = fine_ops.grid.shape, coarse_ops.grid.shape
    constrain = getattr(coarse_ops.fft, "constrain_k", lambda s: s)
    for a, axis in enumerate((-3, -2, -1)):
        # re-pin the pencil sharding after every axis (each intermediate
        # keeps both sharded k axes divisible, so the hint is always valid;
        # without it GSPMD's cost model may replicate small spectra)
        spec = constrain(_truncate_axis(spec, axis, fine[a], coarse[a], rfft and a == 2))
    scale = coarse_ops.grid.num_points / fine_ops.grid.num_points
    return spec * (_mask(fine_ops, coarse_ops, rfft) * scale)


def pad_spec(
    spec: jnp.ndarray, coarse_ops: SpectralOps, fine_ops: SpectralOps
) -> jnp.ndarray:
    """Zero-pad a coarse-layout spectrum into the fine layout (mask + the
    prolongation normalization applied): ``prolong = fine.inv o this o
    coarse.fwd``.  Slices + ``lax.pad`` + add only (sharded-friendly; see
    module docstring), with the fine backend's k-space sharding hint
    re-applied after every axis."""
    rfft = _check_pair(fine_ops, coarse_ops)
    fine, coarse = fine_ops.grid.shape, coarse_ops.grid.shape
    scale = fine_ops.grid.num_points / coarse_ops.grid.num_points
    spec = spec * (_mask(fine_ops, coarse_ops, rfft) * scale)
    constrain = getattr(fine_ops.fft, "constrain_k", lambda s: s)
    for a, axis in enumerate((-3, -2, -1)):
        spec = constrain(_pad_axis(spec, axis, fine[a], coarse[a], rfft and a == 2))
    return spec


@telemetry.annotate("fft.fwd")
def _fwd(ops: SpectralOps, f: jnp.ndarray) -> jnp.ndarray:
    """``ops``' forward transform, under the scope ``SpectralOps``' own
    transforms carry."""
    return ops.fft.fwd(f)


@telemetry.annotate("fft.inv")
def _inv(ops: SpectralOps, spec: jnp.ndarray) -> jnp.ndarray:
    """``ops``' inverse transform, under the scope ``SpectralOps``' own
    transforms carry."""
    return ops.fft.inv(spec)


def restrict(f: jnp.ndarray, fine_ops: SpectralOps, coarse_ops: SpectralOps) -> jnp.ndarray:
    """Sample ``f``'s band-limited interpolant on the coarse grid.

    ``f``: (..., N1, N2, N3) on ``fine_ops.grid``; returns (..., M1, M2, M3).
    """
    return _inv(coarse_ops, restrict_spec(_fwd(fine_ops, f), fine_ops, coarse_ops))


def prolong(g: jnp.ndarray, coarse_ops: SpectralOps, fine_ops: SpectralOps) -> jnp.ndarray:
    """Band-limited interpolation of a coarse field onto the fine grid.

    ``g``: (..., M1, M2, M3) on ``coarse_ops.grid``; returns (..., N1, N2, N3).
    """
    return _inv(fine_ops, pad_spec(_fwd(coarse_ops, g), coarse_ops, fine_ops))


def smooth_restrict(
    f: jnp.ndarray, fine_ops: SpectralOps, coarse_ops: SpectralOps
) -> jnp.ndarray:
    """Gaussian pre-smoothing at the coarse grid's bandwidth, then restrict.

    The sharp cutoff alone is alias-free on a spectral grid but rings on
    images with near-Nyquist content; smoothing at one *coarse* cell width
    (the same filter ``register()`` applies at the fine bandwidth) is
    CLAIRE's coarse-image construction.  One fine ride pair: the Gaussian
    multiplier rides the restriction's own forward transform.
    """
    _check_pair(fine_ops, coarse_ops)
    spec = _fwd(fine_ops, f) * fine_ops._smooth_scale(coarse_ops.grid.spacing)
    return _inv(coarse_ops, restrict_spec(spec, fine_ops, coarse_ops))
