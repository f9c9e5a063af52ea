"""Objective, reduced gradient, and Gauss-Newton Hessian matvec (paper §II-B).

    J[v]   = 1/2 ||rho(1) - rho_R||^2_L2 + beta/2 ||Lap v||^2_L2          (2a)
    g(v)   = beta Lap^2 v + P b,    b = int_0^1 lam grad rho dt           (4)
    H vt   = beta Lap^2 vt + P bt,  bt = int_0^1 lamt grad rho dt (GN)    (5e)

``P`` is the Leray projection in incompressible mode, identity otherwise.
A ``NewtonState`` caches everything reusable across the PCG matvecs of one
Newton iteration: the SL plan (departure points AND the precomputed
``InterpPlan`` interpolation operators — base indices + separable Lagrange
weights, built once by ``planner.make_plan`` and bound per transport by
``semilag._bind``), the state series rho(t), and — a deliberate
memory-for-FFTs trade documented in EXPERIMENTS §Perf — the spectral
gradients grad rho(t_k) for all k.  With those caches a GN Hessian matvec
in incompressible mode needs *zero* transport FFTs and *zero* interpolation
weight constructions (only the gathers/contractions themselves plus the
regularization/Leray diagonal ops), versus 8 n_t FFTs in the paper's
Alg. 2 accounting.

**Transform coalescing** (this PR's hot-path restructuring): every spectral
round trip below rides a ``SpectralOps.batch()`` or an explicitly fused
k-space combine, so the per-stage transform count is minimal:

* ``newton_state`` stage A — ``div v`` (compressible), ``beta Lap^2 v``,
  and the regularization energy all depend only on ``v``: one coalesced
  ride pair instead of three, with the energy read off the shared forward
  spectrum by Parseval (``SpectralBatch.reg_energy`` — it joins no
  inverse ride at all).
* ``evaluate_objective`` (every Armijo trial) — the energy is the same
  spectrum-side reduction, so a trial costs one forward of ``v`` (shared
  with ``div v`` when compressible) and ZERO inverse transforms — one
  ride pair fewer than the eager ``reg_energy`` composition (pinned in
  ``tests/test_coalesce.py``).
* the gradient assembly — ``g = beta Lap^2 v + P b`` reuses stage A's
  ``beta Lap^2 v``; only ``P b`` costs a ride (none when compressible).
* ``gn_hessian_matvec`` — ``beta Lap^2 vt + P bt`` is ONE ride pair
  (``reg_plus_project``); compressible mode skips ``bt``'s transform
  entirely.  The all-to-all count per matvec is pinned ≥2x below the
  uncoalesced composition by ``tests/test_coalesce.py``.
* ``full_hessian_matvec`` — the ``div(lam vt)`` series and the
  ``grad rho~(t)`` series share one coalesced ride pair.

**Cohort axis** (the solves/second lever, ROADMAP item 1): every function
here is rank-polymorphic over a leading subjects axis ``S``.  A cohort
``Problem`` carries image stacks ``rho_R``/``rho_T`` of shape ``(S, N..)``
and a velocity stack ``(S, 3, N..)``; the cached series become
``rho (n_t+1, S, N..)`` / ``grad rho (n_t+1, S, 3, N..)``, the component
axis of vector fields always sits at ``-4``, and ``misfit``/``reg``/
``j_val`` are per-subject ``(S,)``.  All S subjects ride the SAME batched
interp calls (one ghost exchange per transport step on a mesh) and the
SAME coalesced transform rides — amortizing the collective-latency cost
of one solve across the whole cohort (``gn.solve_cohort``).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import semilag
from repro.core.grid import Grid
from repro.core.planner import SLPlan, make_plan
from repro.core.spectral import SpectralOps


class Problem(NamedTuple):
    grid: Grid
    rho_R: jnp.ndarray  # (N..) single subject; (S, N..) cohort
    rho_T: jnp.ndarray
    beta: float  # may be a traced scalar (the cohort driver's one-program continuation)
    n_t: int
    incompressible: bool


class NewtonState(NamedTuple):
    """Per-Newton-iteration cache shared by gradient and all Hessian matvecs.

    Cohort problems prepend a subjects axis: ``v (S,3,N..)``, series
    ``(n_t+1, S, ...)``, ``g (S,3,N..)``, and the scalar diagnostics
    become per-subject ``(S,)``.
    """

    v: jnp.ndarray
    plan: SLPlan
    rho_series: jnp.ndarray  # (n_t+1, N1,N2,N3)
    grad_rho_series: jnp.ndarray  # (n_t+1, 3, N1,N2,N3)
    lam_series: jnp.ndarray  # (n_t+1, N1,N2,N3)
    g: jnp.ndarray  # reduced gradient (3, N1,N2,N3)
    misfit: jnp.ndarray  # 1/2 ||rho(1)-rho_R||^2
    reg: jnp.ndarray  # beta/2 ||Lap v||^2
    j_val: jnp.ndarray


def _project(ops: SpectralOps, field: jnp.ndarray, incompressible: bool) -> jnp.ndarray:
    return ops.leray(field) if incompressible else field


def _norm_sq(grid: Grid, x: jnp.ndarray, cohort: bool) -> jnp.ndarray:
    return grid.norm_sq_per(x) if cohort else grid.norm_sq(x)


@telemetry.annotate("objective.evaluate")
def evaluate_objective(
    v: jnp.ndarray, prob: Problem, ops: SpectralOps, interp=None, plan: SLPlan | None = None
):
    """J(v) — one forward transport + one spectral regularization energy.

    Cohort inputs (``v (S,3,N..)``) return per-subject ``(S,)`` values."""
    cohort = v.ndim == 5
    fd = getattr(ops, "field_dtype", None)
    # Parseval lever: the regularization energy is a spectrum-side reduction
    # on the forward spectrum of v, and (compressible) shares that ONE
    # forward ride with div v for the plan — an Armijo trial pays no
    # dedicated forward/inverse pair for the energy (a2a-pinned by
    # tests/test_coalesce.py).
    with ops.batch() as sb:
        h_reg = sb.reg_energy(v, prob.beta)
        h_div = sb.div(v) if (plan is None and not prob.incompressible) else None
    if plan is None:
        # forward-only plan: line-search trials never transport backward
        plan = make_plan(
            v, prob.grid, ops, prob.n_t, prob.incompressible, interp, adjoint=False,
            divv=None if h_div is None else h_div.get(),
        )
    rho_series = semilag.transport_state(prob.rho_T, plan, interp, field_dtype=fd)
    rho1 = rho_series[-1]
    misfit = 0.5 * _norm_sq(prob.grid, rho1 - prob.rho_R, cohort)
    reg = h_reg.get()
    return misfit + reg, (misfit, reg, rho_series, plan)


@telemetry.annotate("objective.newton_state")
def newton_state(
    v: jnp.ndarray, prob: Problem, ops: SpectralOps, interp=None, lap2=None
) -> NewtonState:
    """Forward + adjoint solves, reduced gradient, and the matvec cache.

    Spectral stage A (everything that depends only on ``v``: ``div v``,
    ``beta Lap^2 v``, ``Lap v``) rides ONE coalesced transform pair; the
    cached gradient series ``grad rho(t_k)`` is one batched ride over all
    time slices; in incompressible mode ``P b`` costs one more.  Cohort
    inputs (``v (S,3,N..)``) share all of those rides across subjects.
    ``lap2`` supplies ``Lap^2 v`` exactly (``gauss_newton.with_lap2``); the
    gradient then reads it instead of transforming ``v``.
    """
    cohort = v.ndim == 5
    fd = getattr(ops, "field_dtype", None)
    # ---- stage A: one ride pair for every v-only spectral op; the
    # regularization energy rides the same forward as a spectrum-side
    # Parseval reduction (no Lap v inverse — 3 fewer inverse fields)
    with ops.batch() as sb:
        h_divv = None if prob.incompressible else sb.div(v)
        h_regv = sb.reg_apply(v, prob.beta) if lap2 is None else None
        h_reg_e = sb.reg_energy(v, prob.beta)
    plan = make_plan(
        v, prob.grid, ops, prob.n_t, prob.incompressible, interp,
        divv=None if h_divv is None else h_divv.get(),
    )
    rho_series = semilag.transport_state(prob.rho_T, plan, interp, field_dtype=fd)
    rho1 = rho_series[-1]

    # adjoint terminal condition lam(1) = rho_R - rho(1)   (eq. 3)
    lam_series = semilag.transport_adjoint(
        prob.rho_R - rho1, plan, interp, field_dtype=fd
    )

    # cache grad rho(t_k): ONE batched spectral gradient over all slices
    # (leading dims pass through both FFT backends; no vmap-of-shard_map);
    # the component axis lands at -4 in both layouts:
    # single (n_t+1, 3, N..), cohort (n_t+1, S, 3, N..)
    grad_rho_series = jnp.moveaxis(ops.grad(rho_series), 0, -4)

    b = semilag.time_integral_b(lam_series, grad_rho_series, plan.dt)
    # eq. (4): g = beta Lap^2 v + P b, with lam(1) = rho_R - rho(1);
    # beta Lap^2 v comes from stage A, so only P b can cost a transform.
    # (sanity: at v=0, <g,w> = <(rho_R-rho_T) grad rho_T, w> = dJ/deps.)
    reg_v = h_regv.get() if lap2 is None else prob.beta * lap2
    g = reg_v + _project(ops, b, prob.incompressible)

    misfit = 0.5 * _norm_sq(prob.grid, rho1 - prob.rho_R, cohort)
    reg = h_reg_e.get()
    return NewtonState(
        v=v,
        plan=plan,
        rho_series=rho_series,
        grad_rho_series=grad_rho_series,
        lam_series=lam_series,
        g=g,
        misfit=misfit,
        reg=reg,
        j_val=misfit + reg,
    )


@telemetry.annotate("objective.gn_hessian_matvec")
def gn_hessian_matvec(
    vtilde: jnp.ndarray,
    state: NewtonState,
    prob: Problem,
    ops: SpectralOps,
    interp=None,
    lap2=None,
) -> jnp.ndarray:
    """Gauss-Newton Hessian action, eq. (5) with the lambda terms dropped.

    Two transport solves (incremental state forward, incremental adjoint
    backward) — both interpolation-only thanks to the grad-rho cache — plus
    the elliptic assembly in ONE coalesced ride pair:
    ``beta Lap^2 vt + P bt`` forwards ``[vt, bt]`` together and inverts the
    3-component combine (incompressible); compressible mode adds ``bt`` in
    real space and transforms only ``vt``.  Cohort states apply S
    independent Hessians to a ``(S, 3, N..)`` stack in the same rides.
    ``lap2`` supplies ``Lap^2 vt`` exactly (PCG carries it): then only
    ``P bt`` is transformed, and nothing at all when compressible.
    """
    rho1_t = semilag.transport_inc_state(vtilde, state.grad_rho_series, state.plan, interp)
    lamt_series = semilag.transport_inc_adjoint(-rho1_t, state.plan, interp)
    bt = semilag.time_integral_b(lamt_series, state.grad_rho_series, state.plan.dt)
    # eq. (5e): H vt = beta Lap^2 vt + P bt, with lam~(1) = -rho~(1);
    # the data block is the Gauss-Newton (J^T J) term — PSD (tested).
    return _elliptic(vtilde, bt, prob, ops, lap2)


def _elliptic(vtilde, bt, prob: Problem, ops: SpectralOps, lap2):
    """``beta Lap^2 vt + P bt``, reading ``Lap^2 vt`` off ``lap2`` when given."""
    if lap2 is not None:
        return prob.beta * lap2 + _project(ops, bt, prob.incompressible)
    if prob.incompressible:
        return ops.reg_plus_project(vtilde, bt, prob.beta, True)
    return ops.reg_apply(vtilde, prob.beta) + bt


@telemetry.annotate("objective.full_hessian_matvec")
def full_hessian_matvec(
    vtilde: jnp.ndarray, state: NewtonState, prob: Problem, ops: SpectralOps, interp=None,
    lap2=None,
) -> jnp.ndarray:
    """FULL Newton Hessian action — paper eq. (5) with every term.

    vs Gauss-Newton this keeps (i) the div(lam vt) source in the incremental
    adjoint (5c) and (ii) the lam grad(rho~) term in b~.  Costs one stored
    rho~(t) series plus ONE extra coalesced ride pair (the batched
    ``div(lam vt)`` series and the batched ``grad rho~(t)`` series share
    it).  Near the solution (lam -> 0) it coincides with GN (tested); away
    from it the data block may be indefinite, which is exactly why the
    paper defaults to GN (§IV-A3).  Single-subject only: cohort solves
    run the Gauss-Newton form (``GNConfig.gauss_newton=True``).
    """
    if vtilde.ndim == 5:
        raise NotImplementedError(
            "full Newton Hessian has no cohort path; use gauss_newton=True"
        )
    rho_t_series = semilag.transport_inc_state_series(
        vtilde, state.grad_rho_series, state.plan, interp
    )
    # div(lam(t_k) vt) for all k and grad rho~(t_k) for all k are mutually
    # independent diagonal ops: one coalesced ride pair for both series
    lam_vt = state.lam_series[:, None] * vtilde[None]  # (n_t+1, 3, N..)
    with ops.batch() as sb:
        h_div = sb.div(lam_vt)  # (n_t+1, N..)
        h_grad = sb.grad(rho_t_series)  # (3, n_t+1, N..)
    lamt_series = semilag.transport_inc_adjoint_newton(
        -rho_t_series[-1], state.lam_series, vtilde, state.plan, ops, interp,
        div_lam_vt=h_div.get(),
    )
    bt = semilag.time_integral_b(lamt_series, state.grad_rho_series, state.plan.dt)
    # second term of b~: int lam(t) grad rho~(t) dt
    grad_rho_t = jnp.swapaxes(h_grad.get(), 0, 1)  # (n_t+1, 3, N..)
    bt = bt + semilag.time_integral_b(state.lam_series, grad_rho_t, state.plan.dt)
    return _elliptic(vtilde, bt, prob, ops, lap2)
