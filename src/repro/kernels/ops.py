"""Jitted dispatch layer over the interpolation kernels.

``method="auto"`` picks the Pallas kernel on TPU when the shape admits a
lane-aligned tile, and the pure-jnp oracle elsewhere (CPU/GPU).  A TPU
shape with no such tile runs the oracle with a warning; an explicit
``"pallas"`` with no valid tile raises.  Off the TPU an explicit
``"pallas"`` runs the kernel in interpret mode (correctness tests only).

The first-class entry is ``make_interp``: an ``Interp`` executor implements
the solver-wide interpolation protocol —

    interp(field, disp)          field (..., N1,N2,N3), leading dims batched
    interp.make_plan(disp)       -> InterpPlan (precomputed operators)
    interp.apply_plan(fields, plan)

``core.planner.make_plan`` builds one ``InterpPlan`` per departure field
through ``make_plan`` and ``core.semilag`` binds ``apply_plan`` so every
transport solve and PCG Hessian matvec of a Newton iteration reuses the
cached weights.  ``repro.dist.halo`` implements the same protocol on the
pencil mesh.
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.kernels import ref
from repro.kernels.tricubic import (
    LANES,
    SUBLANES,
    tricubic_apply_pallas,
    tricubic_displace_pallas_many,
)


def pick_tile(shape, native: bool) -> tuple[int, int, int] | None:
    """Output tile of the Pallas kernel for a (N1, N2, N3) block, or None.

    ``native`` (compiled for the chip): T3 = 128 lanes and T2 = 8 sublanes,
    the only blocks Mosaic accepts.  Interpret mode takes any divisors.
    """
    def best(n, cands):
        return next((c for c in cands if n % c == 0), None)

    n1, n2, n3 = shape
    t1 = best(n1, (8, 4, 2, 1))
    if native:
        t2, t3 = best(n2, (SUBLANES,)), best(n3, (LANES,))
    else:
        t2, t3 = best(n2, (8, 4, 2, 1)), best(n3, (64, 32, 16, 8))
    if t2 is None or t3 is None:
        return None
    return (t1, t2, t3)


class Resolved(NamedTuple):
    method: str  # "ref" | "pallas"
    tile: tuple[int, int, int] | None
    interpret: bool


def resolve(method: str, shape3, tile=None) -> Resolved:
    """Single dispatch policy for a (N1, N2, N3) block (a whole grid, or one
    shard of the mesh path): "auto" -> the Pallas kernel on TPU, the jnp
    oracle elsewhere.  Never falls back in silence: "auto" on a TPU shape
    with no lane-aligned tile warns, an explicit "pallas" with none raises."""
    on_tpu = jax.default_backend() == "tpu"
    if method == "ref" or (method == "auto" and not on_tpu):
        return Resolved("ref", None, False)
    if method not in ("auto", "pallas"):
        raise ValueError(f"unknown interpolation method {method!r}")
    tile = tile or pick_tile(tuple(shape3), native=on_tpu)
    if tile is None:
        why = (f"no Pallas tile for block {tuple(shape3)}: the chip needs "
               f"N2 % {SUBLANES} == 0 and N3 % {LANES} == 0")
        if method == "pallas":
            raise ValueError(why)
        warnings.warn(why + "; interpolating with the jnp gather", stacklevel=3)
        return Resolved("ref", None, False)
    return Resolved("pallas", tuple(tile), not on_tpu)


# ghost budget of the direct kernel entries (``tricubic_displace*``), in
# voxels; the solver sizes its own with ``ghost_budget``
DEFAULT_HALO = 8
# peak speed (physical units on [0, 2 pi)^3) a derived budget covers: 1.5x
# that of the paper's synthetic velocity
BUDGET_SPEED = 1.5
# widest budget the kernel is compiled with (AOT-checked for v5e at C=3 in
# tests/test_tpu_compile.py); a wider need interpolates with the gather
MAX_HALO = 16


def ghost_budget(shape, n_t: int) -> int:
    """Ghost-layer width, in voxels, for departure points of a velocity of
    peak ``BUDGET_SPEED`` over one of ``n_t`` time steps on a ``shape``
    grid of [0, 2 pi)^3: the CFL-style bound ``speed N / (2 pi n_t)`` on
    the finest axis, at least 4.  8 at 128^3 and 16 at 256^3 with n_t 4."""
    return max(4, math.ceil(BUDGET_SPEED * max(shape) / (2 * math.pi * n_t)))


def _count_overflow(need, halo):
    telemetry.counter("halo_budget_exceeded", echo=True, required=float(need),
                      budget=halo, mode="error")


def poison_beyond_halo(out, need, halo):
    """NaN-poison ``out`` when ``need`` (ceil max |disp|) exceeds the ghost
    budget ``halo``, counting a ``halo_budget_exceeded`` event: a kernel
    fed a displacement beyond its ghost layer returns garbage, and NaNs stop
    the line search / health guard instead.  No gather is compiled in: on
    the TPU the XLA gather takes minutes to compile at 128^3."""
    ok = need <= halo
    jax.lax.cond(ok, lambda n: None, lambda n: jax.debug.callback(_count_overflow, n, halo),
                 need)
    return out + jnp.where(ok, 0.0, jnp.nan).astype(out.dtype)


def _per_subject(kernel, fields, *operands):
    """A single-subject kernel over a cohort: ``fields (..., S, N..)``
    against per-subject ``operands`` ``(S, ...)``, one call per subject."""
    outs = [kernel(fields[..., s, :, :, :], *(x[s] for x in operands))
            for s in range(operands[0].shape[0])]
    return jnp.stack(outs, axis=-4)


def _pallas_displace(fields, disp, rs, halo):
    shape3 = fields.shape[-3:]
    out = tricubic_displace_pallas_many(
        fields.reshape((-1,) + shape3), disp, tile=rs.tile, halo=halo,
        interpret=rs.interpret,
    )
    return out.reshape(fields.shape)


def tricubic_displace(
    field: jnp.ndarray,
    disp: jnp.ndarray,
    *,
    method: str = "auto",
    halo: int = DEFAULT_HALO,
    tile: tuple[int, int, int] | None = None,
) -> jnp.ndarray:
    """field (N1,N2,N3) sampled at x + disp; disp (3,N1,N2,N3), grid units."""
    rs = resolve(method, field.shape, tile)
    if rs.method == "ref":
        return ref.tricubic_displace(field, disp)
    return poison_beyond_halo(_pallas_displace(field, disp, rs, halo),
                              jnp.ceil(max_displacement(disp)), halo)


def tricubic_displace_many(
    fields: jnp.ndarray,
    disp: jnp.ndarray,
    *,
    method: str = "auto",
    halo: int = DEFAULT_HALO,
    tile: tuple[int, int, int] | None = None,
) -> jnp.ndarray:
    """Batched multi-field entry: ``fields`` (..., N1,N2,N3), leading dims
    are channels sharing one weight construction / one kernel launch.

    A cohort displacement ``disp (S, 3, N..)`` pairs subject ``s`` with the
    ``-4`` axis of ``fields`` (``(..., S, N..)``); the Pallas path runs one
    kernel call per subject."""
    rs = resolve(method, fields.shape[-3:], tile)
    if rs.method == "ref":
        return ref.tricubic_displace_many(fields, disp)
    if disp.ndim == 5:  # cohort: per-subject departure fields
        out = _per_subject(lambda f, d: _pallas_displace(f, d, rs, halo), fields, disp)
    else:
        out = _pallas_displace(fields, disp, rs, halo)
    return poison_beyond_halo(out, jnp.ceil(max_displacement(disp)), halo)


class Interp:
    """Plan-aware single-device interpolation executor (see module docstring).

    Its three entries trace under the named scopes ``interp.displace``,
    ``interp.plan`` and ``interp.apply`` (``telemetry.annotate``): a
    profile finds the interpolation's device ops by those names, whatever
    kernel implements them.

    ``method``/``halo``/``tile`` follow ``tricubic_displace``: on the Pallas
    path a displacement beyond ``halo`` NaN-poisons the result
    (``poison_beyond_halo``, read off the plan's cached ``halo_need``).
    ``plan_dtype`` packs the cached ``InterpPlan`` weights (e.g.
    ``jnp.bfloat16`` halves the plan's memory; contraction stays f32 — see
    ``ref.make_interp_plan``).
    """

    def __init__(self, method: str = "auto", halo: int = DEFAULT_HALO, tile=None,
                 plan_dtype=None):
        self.method = method
        self.halo = halo
        self.tile = tile
        self.plan_dtype = plan_dtype

    @telemetry.annotate("interp.displace")
    def __call__(self, field: jnp.ndarray, disp: jnp.ndarray) -> jnp.ndarray:
        if field.ndim == 3:
            return tricubic_displace(
                field, disp, method=self.method, halo=self.halo, tile=self.tile
            )
        return tricubic_displace_many(
            field, disp, method=self.method, halo=self.halo, tile=self.tile
        )

    @telemetry.annotate("interp.plan")
    def make_plan(self, disp: jnp.ndarray) -> ref.InterpPlan:
        return ref.make_interp_plan(disp, dtype=self.plan_dtype)

    @telemetry.annotate("interp.apply")
    def apply_plan(self, fields: jnp.ndarray, plan: ref.InterpPlan) -> jnp.ndarray:
        shape3 = fields.shape[-3:]
        rs = resolve(self.method, shape3, self.tile)
        if rs.method == "ref":
            return ref.interp_apply(fields, plan)

        def kernel(f, ib, w):
            out = tricubic_apply_pallas(
                f.reshape((-1,) + shape3), ref.InterpPlan(ib, w, plan.halo_need),
                tile=rs.tile, halo=self.halo, interpret=rs.interpret,
            )
            return out.reshape(f.shape)

        if plan.ib.ndim == 5:  # cohort plan: one kernel call per subject
            out = _per_subject(kernel, fields, plan.ib, plan.w)
        else:
            out = kernel(fields, plan.ib, plan.w)
        return poison_beyond_halo(out, plan.halo_need, self.halo)

    def widened(self, need: float, shape3) -> "Interp | None":
        """The executor for a step whose departure points reached ``need``
        voxels, or None when this one covers it (or runs no kernel): the
        Pallas kernel with 1.5x the need as its budget, or — past
        ``MAX_HALO`` — the exact jnp gather, with a warning."""
        if not math.isfinite(need) or need <= self.halo:
            return None
        if resolve(self.method, shape3, self.tile).method != "pallas":
            return None
        halo = math.ceil(1.5 * need)
        telemetry.counter("interp.halo_widened", required=float(need), budget=self.halo,
                          widened_to=min(halo, MAX_HALO + 1))
        if halo > MAX_HALO:
            warnings.warn(f"departure points {need:g} voxels away exceed the Pallas "
                          f"kernel's widest ghost budget ({MAX_HALO}); interpolating "
                          "with the jnp gather", stacklevel=2)
            return Interp("ref", plan_dtype=self.plan_dtype)
        return Interp(self.method, halo=halo, tile=self.tile, plan_dtype=self.plan_dtype)


def make_interp(method: str = "auto", halo: int = DEFAULT_HALO, tile=None,
                plan_dtype=None) -> Interp:
    """Factory for the solver's ``interp=`` slots (kept for API symmetry
    with ``repro.dist.halo.make_halo_interp``)."""
    return Interp(method=method, halo=halo, tile=tile, plan_dtype=plan_dtype)


def tricubic_points(field: jnp.ndarray, coords: jnp.ndarray, chunk: int | None = None) -> jnp.ndarray:
    """Arbitrary (unbounded) query points — oracle path only."""
    if chunk:
        return ref.tricubic_points_chunked(field, coords, chunk)
    return ref.tricubic_points(field, coords)


@functools.partial(jax.jit, static_argnames=())
def max_displacement(disp: jnp.ndarray) -> jnp.ndarray:
    """Per-axis max |disp| in grid units — the planner's halo requirement."""
    return jnp.max(jnp.abs(disp))
