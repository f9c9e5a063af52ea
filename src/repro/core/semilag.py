"""Semi-Lagrangian transport solvers (paper §III-B2, eq. (6)-(7), Alg. 2).

Unconditionally stable RK2 along characteristics, so ``n_t = 4`` time steps
suffice (the paper's setting) and storing all time slices is feasible —
which the Gauss-Newton Hessian needs (eq. (5) requires rho(t) at all t).

Every solver takes an ``SLPlan`` (departure points + precomputed
``InterpPlan`` operators, built once per velocity — the paper's planner)
and an ``interp`` callable so the same code runs single-device (the
``repro.kernels.ops.Interp`` executor over the oracle/Pallas kernels) and
distributed (``repro.dist.halo``'s ghost-layer exchange, pre-wired as
``DistContext.interp``).

Interp contract (the **batched multi-field** protocol):

    interp(fields, disp)           fields (..., N1,N2,N3); leading dims are
                                   channels evaluated at the same departure
                                   points in one call (one ghost-exchange
                                   round on a mesh, one kernel launch)
    interp.make_plan(disp)         optional: precompute an InterpPlan
    interp.apply_plan(fields, p)   optional: planned apply

``_bind`` resolves the fastest available path once per transport solve:
whenever the ``SLPlan`` carries a cached ``InterpPlan`` and the interp
implements ``apply_plan``, every step of the scan hits precomputed weights;
otherwise it degrades to the plain ``interp(fields, disp)`` form (which
still batches channels).  The transports below exploit the batching by
stacking the fields of each RK2 stage — e.g. ``lam`` with ``lam * div v``
in the compressible adjoint — into single calls.

General scheme for  d_t nu + v . grad nu = f  (paper eq. (7)):

    nu0X  = nu(X, t)            (interpolated at departure points)
    f0X   = f(., t) at X        (f formed on the grid, then interpolated)
    nu*   = nu0X + dt f0X
    f*    = f(., t+dt) at x     (on the grid)
    nu(x, t+dt) = nu0X + dt/2 (f0X + f*)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.planner import SLPlan
from repro.kernels import ops as kops


def _bind(interp, disp, iplan):
    """Resolve one displacement field to a batched applier ``fields -> out``.

    Preference order: cached-plan apply (planner-built operators, the
    plan-once/apply-many fast path) > the interp's own planned path >
    plain per-call interpolation.
    """
    if interp is None:  # the platform default: Pallas on TPU, gather elsewhere
        interp = kops.make_interp()
        iplan = interp.make_plan(disp) if iplan is None else iplan
    apply_plan = getattr(interp, "apply_plan", None)
    if iplan is not None and apply_plan is not None:
        return lambda fields: apply_plan(fields, iplan)
    return lambda fields: interp(fields, disp)


def _bind_fwd(plan: SLPlan, interp):
    return _bind(interp, plan.disp_fwd, plan.iplan_fwd)


def _bind_adj(plan: SLPlan, interp):
    if plan.disp_adj is None:
        raise ValueError(
            "forward-only SLPlan (make_plan(adjoint=False)) has no adjoint "
            "departure field; rebuild with adjoint=True for backward transports"
        )
    return _bind(interp, plan.disp_adj, plan.iplan_adj)


# --------------------------------------------------------------------------- #
# state equation (2b): pure advection, forward in time
# --------------------------------------------------------------------------- #
def transport_state(
    rho0: jnp.ndarray, plan: SLPlan, interp=None, field_dtype=None
) -> jnp.ndarray:
    """Solve d_t rho + v.grad rho = 0; returns all slices (n_t+1, N1,N2,N3).

    ``field_dtype`` (e.g. ``jnp.bfloat16``) selects the storage dtype of the
    transported stack: the initial condition is cast once and every slice
    inherits it (the planned interpolation applies in >= f32 and casts back
    to the field dtype — ``kernels/ref.py``), halving the series' footprint
    and the ghost-exchange bytes of each step on a mesh.
    """
    if field_dtype is not None:
        rho0 = rho0.astype(field_dtype)
    at_fwd = _bind_fwd(plan, interp)

    def step(rho, _):
        nxt = at_fwd(rho)
        return nxt, nxt

    _, series = jax.lax.scan(step, rho0, None, length=plan.n_t)
    return jnp.concatenate([rho0[None], series], axis=0)


# --------------------------------------------------------------------------- #
# adjoint equation (3): -d_t lam - div(v lam) = 0, backward in time.
# In tau = 1-t:  d_tau lam + (-v).grad lam = lam div v.
# Incompressible (div v = 0): pure advection along -v.
# --------------------------------------------------------------------------- #
def transport_adjoint(
    lam1: jnp.ndarray, plan: SLPlan, interp=None, field_dtype=None
) -> jnp.ndarray:
    """Returns lam at all *t*-slices, index k = t_k (so [..., -1] is t=1).

    ``field_dtype``: storage dtype of the adjoint stack (see
    ``transport_state``)."""
    if field_dtype is not None:
        lam1 = lam1.astype(field_dtype)
    at_adj = _bind_adj(plan, interp)
    dt = plan.dt

    if plan.divv is None:

        def step(lam, _):
            nxt = at_adj(lam)
            return nxt, nxt

    else:
        divv = plan.divv

        def step(lam, _):
            # lam and lam*divv share one batched interpolation (C=2):
            # one ghost exchange on a mesh instead of two.  The carry keeps
            # lam's storage dtype even if divv is wider (mixed field_dtype).
            lam0X, f0X = at_adj(jnp.stack([lam, lam * divv]))
            lam_star = lam0X + dt * f0X
            f_star = lam_star * divv
            nxt = (lam0X + 0.5 * dt * (f0X + f_star)).astype(lam.dtype)
            return nxt, nxt

    _, series_tau = jax.lax.scan(step, lam1, None, length=plan.n_t)
    series = jnp.concatenate([lam1[None], series_tau], axis=0)
    return series[::-1]  # tau-order -> t-order


# --------------------------------------------------------------------------- #
# incremental state equation (5a) (Alg. 2):
#   d_t rho~ + v.grad rho~ = -v~ . grad rho(t),  rho~(0) = 0
# --------------------------------------------------------------------------- #
def transport_inc_state(
    vtilde: jnp.ndarray,
    grad_rho_series: jnp.ndarray,  # (n_t+1, 3, N1,N2,N3), precomputed spectrally
    plan: SLPlan,
    interp=None,
) -> jnp.ndarray:
    """Returns rho~(1) (only the final slice is needed for Gauss-Newton)."""
    at_fwd = _bind_fwd(plan, interp)
    dt = plan.dt
    # carry in the promoted compute dtype: under bf16 field storage the
    # source term -v~.grad rho may be wider than the stored series (v~ is
    # the f32 PCG iterate), and a scan carry must keep one dtype throughout
    ct = jnp.result_type(vtilde, grad_rho_series)
    rho0 = jnp.zeros_like(grad_rho_series[0][..., 0, :, :, :], dtype=ct)

    def source(k):
        # f(., t_k) = -v~ . grad rho(t_k) on the grid; the component axis
        # sits at -4 for both the single (3,N..) and cohort (S,3,N..) layouts
        return -jnp.sum(vtilde * grad_rho_series[k], axis=-4)

    def step(carry, k):
        rt = carry
        rt0X, f0X = at_fwd(jnp.stack([rt, source(k)]))  # C=2 batched
        f_star = source(k + 1)
        nxt = (rt0X + 0.5 * dt * (f0X + f_star)).astype(ct)
        return nxt, None

    rho1, _ = jax.lax.scan(step, rho0, jnp.arange(plan.n_t))
    return rho1


# --------------------------------------------------------------------------- #
# incremental adjoint (5c), Gauss-Newton form (drop lambda terms):
#   -d_t lam~ - div(lam~ v) = 0,  lam~(1) = -rho~(1)
# Same operator as the adjoint equation.
# --------------------------------------------------------------------------- #
def transport_inc_adjoint(lam1: jnp.ndarray, plan: SLPlan, interp=None) -> jnp.ndarray:
    return transport_adjoint(lam1, plan, interp)


# --------------------------------------------------------------------------- #
# incremental adjoint, FULL NEWTON form (paper eq. (5c) with all terms):
#   -d_t lam~ - div(lam~ v + lam vt) = 0,  lam~(1) = -rho~(1)
# In tau: d_tau lam~ + (-v).grad lam~ = lam~ div v + div(lam(t) vt).
# Needs lam(t) at every slice (stored by newton_state) and one spectral
# divergence per step for the div(lam vt) source.
# --------------------------------------------------------------------------- #
def transport_inc_adjoint_newton(
    lam1: jnp.ndarray,
    lam_series: jnp.ndarray,  # (n_t+1, N..) in t-order
    vtilde: jnp.ndarray,
    plan: SLPlan,
    spectral_ops,
    interp=None,
    div_lam_vt: jnp.ndarray | None = None,
) -> jnp.ndarray:
    at_adj = _bind_adj(plan, interp)
    dt = plan.dt
    n_t = plan.n_t
    divv = plan.divv  # None in incompressible mode

    if div_lam_vt is None:
        # div(lam(t_k) vt) on the grid, all slices in one batched spectral
        # call; the full-Newton matvec (objective.full_hessian_matvec)
        # precomputes this series so it can coalesce the ride with the
        # grad rho~(t) series instead
        div_lam_vt = spectral_ops.div(lam_series[:, None] * vtilde[None])  # (n_t+1, N..)

    def source(lam_t, k):
        f = div_lam_vt[k]
        if divv is not None:
            f = f + lam_t * divv
        return f

    def step(carry, j):
        lamt = carry
        k = n_t - j  # current t-index (tau_j = 1 - t)
        lam0X, f0X = at_adj(jnp.stack([lamt, source(lamt, k)]))  # C=2 batched
        lam_star = lam0X + dt * f0X
        f_star = source(lam_star, k - 1)
        nxt = (lam0X + 0.5 * dt * (f0X + f_star)).astype(lam1.dtype)
        return nxt, nxt

    _, series_tau = jax.lax.scan(step, lam1, jnp.arange(n_t))
    series = jnp.concatenate([lam1[None], series_tau], axis=0)
    return series[::-1]  # t-order


def transport_inc_state_series(
    vtilde: jnp.ndarray, grad_rho_series: jnp.ndarray, plan: SLPlan, interp=None
) -> jnp.ndarray:
    """Like transport_inc_state but returns ALL slices (full Newton needs
    grad rho~(t_k) for the second b~ term)."""
    at_fwd = _bind_fwd(plan, interp)
    dt = plan.dt
    # promoted-dtype carry: see transport_inc_state
    ct = jnp.result_type(vtilde, grad_rho_series)
    rho0 = jnp.zeros_like(grad_rho_series[0][..., 0, :, :, :], dtype=ct)

    def source(k):
        return -jnp.sum(vtilde * grad_rho_series[k], axis=-4)

    def step(carry, k):
        rt = carry
        rt0X, f0X = at_fwd(jnp.stack([rt, source(k)]))
        f_star = source(k + 1)
        nxt = (rt0X + 0.5 * dt * (f0X + f_star)).astype(ct)
        return nxt, nxt

    _, series = jax.lax.scan(step, rho0, jnp.arange(plan.n_t))
    return jnp.concatenate([rho0[None], series], axis=0)


# --------------------------------------------------------------------------- #
# time quadrature:  b = int_0^1 lam(t) grad rho(t) dt   (trapezoidal)
# --------------------------------------------------------------------------- #
def time_integral_b(lam_series: jnp.ndarray, grad_rho_series: jnp.ndarray, dt: float) -> jnp.ndarray:
    """lam_series (n_t+1, N..), grad_rho_series (n_t+1, 3, N..) -> (3, N..).

    Cohort layouts — lam (n_t+1, S, N..), grad (n_t+1, S, 3, N..) — yield
    the per-subject stack (S, 3, N..)."""
    n = lam_series.shape[0]
    w = jnp.full((n,), dt, dtype=jnp.float32).at[0].mul(0.5).at[-1].mul(0.5)
    # critical accumulation: the time quadrature sums n_t+1 products, so
    # bf16-stored series (SpectralOps field_dtype) are upcast and the
    # contraction runs in >= f32 regardless of the storage dtype
    acc = jnp.promote_types(jnp.result_type(lam_series, grad_rho_series), jnp.float32)
    lam_series = lam_series.astype(acc)
    grad_rho_series = grad_rho_series.astype(acc)
    if lam_series.ndim == 5:  # cohort
        return jnp.einsum("t,tsxyz,tscxyz->scxyz", w, lam_series, grad_rho_series,
                          precision=jax.lax.Precision.HIGHEST)
    # HIGHEST: a DEFAULT-precision f32 contraction runs in one bf16 pass on
    # the TPU's MXU, ~1e-3 relative error in every gradient
    return jnp.einsum("t,txyz,tcxyz->cxyz", w, lam_series, grad_rho_series,
                      precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------------- #
# deformation map (1): d_t y + v.grad y = 0, y(x,0) = x.
# Solved for the periodic displacement u = y - x:
#   d_t u + v.grad u = -v,  u(0) = 0.
# --------------------------------------------------------------------------- #
def deformation_displacement(v: jnp.ndarray, plan: SLPlan, interp=None) -> jnp.ndarray:
    """Returns u(1) (3, N1,N2,N3) in *physical* units; y1 = x + u.

    A cohort velocity ``(S, 3, N..)`` returns per-subject displacements of
    the same shape (the component axis is swapped into the interp channel
    slot around each batched call)."""
    at = _bind_fwd(plan, interp)
    if v.ndim == 5:  # cohort: interp wants the subject axis at -4
        at_fwd = lambda x: jnp.swapaxes(at(jnp.swapaxes(x, 0, 1)), 0, 1)
    else:
        at_fwd = at
    dt = plan.dt
    u0 = jnp.zeros_like(v)
    f = -v
    # f is time-independent, so f(X) is the same every step: interpolate the
    # 3 components once, outside the scan (C=3 batched)
    f0X = at_fwd(f)

    def step(u, _):
        u0X = at_fwd(u)  # C=3 batched
        nxt = u0X + 0.5 * dt * (f0X + f)
        return nxt, None

    u1, _ = jax.lax.scan(step, u0, None, length=plan.n_t)
    return u1
