"""Inexact, preconditioned Gauss-Newton-Krylov solver (paper §III-A).

* Newton step from PCG on ``H(v) vt = -g(v)`` with the spectral
  preconditioner ``(beta Lap^2)^{-1}`` (mesh-independent; the paper's choice).
* Inexact solves: Eisenstat-Walker *quadratic* forcing
  ``eta_k = min(eta_max, sqrt(||g_k|| / ||g_0||))`` (paper §IV-A3).
* Globalization: Armijo backtracking line search.
* Optional parameter continuation on beta (paper §III-A).

The whole Newton iteration (plan + forward + adjoint + gradient + PCG +
line search) is one jittable function — on the production mesh this gives
XLA a single program per iteration to schedule collectives in, while the
Python driver loop stays checkpointable.
Its four phases trace under the named scopes ``gn.gradient`` (state,
adjoint, gradient and its norm), ``gn.pcg`` (the preconditioned solve and
the descent safeguard), ``gn.line_search`` (the Armijo trials) and
``gn.update`` (acceptance, the carried ``Lap^2 v`` and the health guard),
which split the step's device ops with no overlap.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import objective as obj
from repro.core.grid import Grid
from repro.core.planner import required_halo
from repro.core.spectral import SpectralOps
from repro.kernels import ops as kops
from repro import telemetry
from repro.resilience import health


@dataclasses.dataclass(frozen=True)
class GNConfig:
    beta: float = 1e-2
    n_t: int = 4
    incompressible: bool = False
    max_newton: int = 20
    gtol: float = 1e-2  # relative gradient tolerance (paper: 1e-2)
    max_cg: int = 100
    eta_max: float = 0.5  # forcing-term cap
    armijo_c1: float = 1e-4
    max_line_search: int = 10
    beta_continuation: tuple[float, ...] = ()  # e.g. (1e-1, 1e-2): warm starts
    # "auto": the Pallas kernel on TPU (the XLA gather there is ~27x slower
    # at 128^3 and takes minutes to compile), the jnp gather elsewhere
    interp_method: str = "auto"  # "ref" | "pallas" | "auto"
    # e.g. "bfloat16": pack InterpPlan weights.  Local-executor only — an
    # explicit interp= (the distributed path) carries its own setting via
    # DistContext(plan_dtype=...) / make_halo_interp(plan_dtype=...).
    plan_dtype: str | None = None
    # e.g. "bfloat16": storage dtype of the transport/FFT field path (the
    # SL-transported stacks and every real field the spectral operators
    # return).  Applies to the SpectralOps this solver builds itself; an
    # explicit ops= carries its own via SpectralOps(field_dtype=...) /
    # DistContext(field_dtype=...).  Critical accumulations stay >= f32:
    # inner products (grid.inner), the time quadrature, the k-space
    # scalings, and the PCG recursion (guarded in newton_iteration).
    field_dtype: str | None = None
    # tuning-cache consult for the perf knobs above: "cache" fills knobs
    # still at their defaults from the repro.autotune cache (missing cache
    # == no-op), "off" disables, "sweep" additionally sweeps on a miss.
    autotune: str = "cache"
    # DEPRECATED no-op: the transform-coalesced hot path (SpectralBatch +
    # fused k-space assemblies in core/objective.py) is now unconditional
    # and numerically identical to the old fused=True routing.  Setting it
    # True emits a DeprecationWarning; the field will be removed.
    fused_elliptic: bool = False
    gauss_newton: bool = True  # False: full Newton Hessian (paper eq. (5), all terms)

    def __post_init__(self):
        if self.fused_elliptic:
            warnings.warn(
                "GNConfig.fused_elliptic is deprecated and has no effect: the "
                "transform-coalesced elliptic assembly (core/objective.py + "
                "SpectralBatch) is unconditional and numerically identical to "
                "the old fused=True routing",
                DeprecationWarning,
                stacklevel=2,
            )


class PCGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray
    rel_res: jnp.ndarray


class NewtonLog(NamedTuple):
    j_val: jnp.ndarray
    misfit: jnp.ndarray
    reg: jnp.ndarray
    gnorm: jnp.ndarray
    cg_iters: jnp.ndarray
    step_len: jnp.ndarray
    ls_iters: jnp.ndarray | int = 0  # Armijo backtracking trials
    # in-graph health code (``repro.resilience.health``): scalar for the
    # single solve, per-subject (S,) for the cohort step
    status: jnp.ndarray | int = 0
    # ghost voxels the iteration's departure points reached: the Newton
    # state's plan and the full (alpha = 1) trial step.  ``solve`` widens
    # its own Pallas budget when this exceeds it
    halo_need: jnp.ndarray | float = 0.0


class Iterate(NamedTuple):
    """A solver iterate: the velocity ``v`` and ``lap2 = Lap^2 v``, carried
    exactly.  ``beta Lap^2 v`` recomputed from ``v``'s f32 values carries
    their roundoff amplified by ``|k|^4`` — at 128^3 ~20% of the initial
    gradient norm, so an f32 solve could never reach gtol 1e-2.  Every
    solver path (``newton_iteration``, the cohort step, ``solve``,
    ``solve_cohort``, the serving slots) carries this pair; PCG carries the
    images of its directions the same way."""

    v: jnp.ndarray
    lap2: jnp.ndarray


def with_lap2(v, ops, cold: bool = False) -> Iterate:
    """The ``Iterate`` of ``v``.  A cold start (``v = 0``) has an exact zero
    image; a warm start pays one transform, once."""
    if cold:
        return Iterate(v, jnp.zeros_like(v))
    return Iterate(v, ops.biharmonic(v).astype(v.dtype))


def _primary(t):
    """The vector itself of a PCG iterate that may carry its image."""
    return t.v if isinstance(t, Iterate) else t


def _axpy(a, x, y):
    """``y + a x`` over a vector or an ``Iterate``."""
    return jax.tree_util.tree_map(lambda xi, yi: yi + a * xi, x, y)


def pcg(
    matvec: Callable,
    b: jnp.ndarray,
    precond: Callable,
    inner: Callable,
    rtol: jnp.ndarray,
    max_iter: int,
) -> PCGResult:
    """Matrix-free preconditioned conjugate gradients (lax.while_loop).

    Counts every Hessian matvec (the paper's Table V metric).

    ``precond`` may return an ``Iterate`` ``(z, Lap^2 z)``.  The search
    directions and the solution are linear combinations of the ``z``'s, so
    PCG then carries their images too: ``matvec`` receives ``Iterate``
    directions and ``x`` comes back as one.
    """
    bnorm = jnp.sqrt(inner(b, b))
    r0 = b
    z0 = precond(r0)
    x0 = jax.tree_util.tree_map(jnp.zeros_like, z0)
    rz0 = inner(r0, _primary(z0))

    def cond(c):
        x, r, p, rz, it = c
        return jnp.logical_and(it < max_iter, jnp.sqrt(inner(r, r)) > rtol * bnorm)

    def body(c):
        x, r, p, rz, it = c
        hp = matvec(p)
        php = inner(_primary(p), hp)
        alpha = rz / jnp.maximum(php, 1e-30)
        x = _axpy(alpha, p, x)
        r = r - alpha * hp
        z = precond(r)
        rz_new = inner(r, _primary(z))
        p = _axpy(rz_new / jnp.maximum(rz, 1e-30), p, z)
        return (x, r, p, rz_new, it + 1)

    x, r, _, _, it = jax.lax.while_loop(cond, body, (x0, r0, z0, rz0, jnp.int32(0)))
    return PCGResult(x=x, iters=it, rel_res=jnp.sqrt(inner(r, r)) / jnp.maximum(bnorm, 1e-30))


def pcg_masked(
    matvec: Callable,
    b: jnp.ndarray,
    precond: Callable,
    inner_per: Callable,
    rtol: jnp.ndarray,
    max_iter: int,
    active: jnp.ndarray,
) -> PCGResult:
    """Per-subject masked PCG over a cohort stack ``b (S, 3, N..)``.

    All subjects advance in lockstep through the SAME batched matvec (one
    set of transform/exchange rides per iteration), but each subject runs
    its OWN scalar-``pcg`` recursion: ``rtol``/``active`` are per-subject
    ``(S,)``, a subject whose residual test or iteration cap trips freezes
    (``x``/``r``/``p``/``rz`` masked in place, zero contribution from then
    on), and the loop ends when no subject is live.  Live trajectories are
    identical to independent ``pcg`` runs up to batched-transform roundoff.
    ``precond`` may return ``Iterate`` pairs, carried as in ``pcg``.

    ``iters`` is per-subject ``(S,)`` — the paper's Table V matvec count as
    a billing meter: a retired (or never-active) subject accrues nothing.
    """

    def bc(s):  # (S,) -> (S, 1, 1, 1, 1): broadcast over field dims
        return s.reshape(s.shape + (1,) * (b.ndim - 1))

    bnorm = jnp.sqrt(inner_per(b, b))
    r0 = b
    z0 = precond(r0)
    x0 = jax.tree_util.tree_map(jnp.zeros_like, z0)
    rz0 = inner_per(r0, _primary(z0))
    iters0 = jnp.zeros((b.shape[0],), jnp.int32)

    def live(r, iters):
        return active & (jnp.sqrt(inner_per(r, r)) > rtol * bnorm) & (iters < max_iter)

    def cond(c):
        x, r, p, rz, iters = c
        return jnp.any(live(r, iters))

    def body(c):
        x, r, p, rz, iters = c
        lv = live(r, iters)
        hp = matvec(p)
        php = inner_per(_primary(p), hp)
        alpha = jnp.where(lv, rz / jnp.maximum(php, 1e-30), 0.0)
        x = _axpy(bc(alpha), p, x)
        r = r - bc(alpha) * hp
        z = precond(r)
        rz_new = inner_per(r, _primary(z))
        beta_cg = jnp.where(lv, rz_new / jnp.maximum(rz, 1e-30), 0.0)
        p = jax.tree_util.tree_map(
            lambda zi, pi: jnp.where(bc(lv), zi + bc(beta_cg) * pi, pi), z, p
        )
        rz = jnp.where(lv, rz_new, rz)
        return (x, r, p, rz, iters + lv.astype(jnp.int32))

    x, r, _, _, iters = jax.lax.while_loop(cond, body, (x0, r0, z0, rz0, iters0))
    rel = jnp.sqrt(inner_per(r, r)) / jnp.maximum(bnorm, 1e-30)
    return PCGResult(x=x, iters=iters, rel_res=rel)


def _tuned_cfg(cfg: GNConfig, grid: Grid, ops) -> GNConfig:
    """Fill still-at-default perf knobs of ``cfg`` from the tuning cache.

    No-op when ``cfg.autotune == "off"``, when the cache has no entry for
    this ``(grid, devices, beta)`` cell, or when every knob was set
    explicitly (an explicit value always wins — the resolver only touches
    knobs still at their dataclass defaults).  Lazy import keeps
    ``repro.autotune`` out of the core dependency graph.
    """
    if cfg.autotune == "off":
        return cfg
    from repro import autotune

    return autotune.consult_gn(cfg, grid, ops)


def _lap2_of_precond(r, beta):
    """``Lap^2 z`` of the spectral preconditioner's output ``z = P r``,
    exactly: P inverts ``beta Lap^2`` on every mode but the mean, which it
    passes through (and ``Lap^2`` maps to zero).  In incompressible mode
    ``r`` already lies in the Leray range, where the projection is the
    identity.  No transform, so no roundoff amplified by ``|k|^4``."""
    return (r - jnp.mean(r, axis=(-3, -2, -1), keepdims=True)) / beta


def _halo_need(plan, v, grid: Grid):
    """Ghost voxels a velocity's transports reach: the plan's bound, or —
    when its departure solve was itself cut short (NaN) — the Euler
    predictor's reach ``dt max|v| / h``, which bounds it."""
    need = required_halo(plan)
    h = jnp.asarray(grid.spacing, need.dtype).reshape(3, 1, 1, 1)
    reach = jnp.ceil(plan.dt * jnp.max(jnp.abs(v) / h))
    return jnp.where(jnp.isnan(need), reach, need)


def _interp_fn(cfg: GNConfig, grid: Grid):
    # plan-aware executor: core.planner.make_plan caches an InterpPlan per
    # departure field through it, so every PCG Hessian matvec / line-search
    # transport of an iteration reuses precomputed interpolation weights;
    # the Pallas path's ghost budget follows the grid and the time step
    return kops.make_interp(method=cfg.interp_method, plan_dtype=cfg.plan_dtype,
                            halo=kops.ghost_budget(grid.shape, cfg.n_t))


def newton_iteration(
    it: Iterate,
    g0_forcing: jnp.ndarray,
    prob: obj.Problem,
    ops: SpectralOps,
    cfg: GNConfig,
    interp=None,
    precond=None,
):
    """One globalized inexact Gauss-Newton step from the ``Iterate`` ``it``.
    Returns (Iterate, NewtonLog).

    ``g0_forcing`` is the Eisenstat-Walker *forcing* reference only — the
    denominator in ``eta = min(eta_max, sqrt(gnorm / g0_forcing))``.  It is
    deliberately decoupled from the convergence reference (``solve``'s
    ``g0_ref``): a warm-started multilevel stage passes its own first-iterate
    gradient norm here, so PCG is solved loosely again (eta near eta_max)
    instead of to the near-machine tolerance that conflating the two
    references forced (``gnorm/g0_ref`` is already ~gtol on a warm level,
    driving eta -> sqrt(gtol) * 0 and over-solving every inner system).
    Pass a tiny sentinel (e.g. ``1e-30``) on the first call of a stage to
    get ``eta = eta_max`` — the classical cold-start choice.

    ``precond`` is an optional factory ``(state, prob) -> (r -> z)``
    replacing the default spectral preconditioner — e.g. the two-level or
    V-cycle multigrid preconditioners built by ``repro.multilevel.precond``.
    It is invoked once per Newton iteration with the fresh ``NewtonState``
    and the current ``Problem`` (whose ``beta`` tracks the continuation
    schedule) so it can assemble state-dependent coarse operators inside the
    same jit program (the V-cycle restricts the state's cached
    ``grad rho``/departure fields right here — Galerkin-consistent coarse
    Hessians with zero extra transport solves).  A factory may carry a
    static ``fine_equiv_cost`` attribute — the fine-grid-equivalent matvec
    cost of one application — which ``solve`` folds into
    ``precond_fine_equiv_matvecs`` (PCG applies the preconditioner
    ``iters + 1`` times per solve).  The Armijo steepest-descent safeguard always uses the cheap
    spectral preconditioner: the safeguard direction only needs descent, and
    a custom factory may be arbitrarily expensive (XLA's select evaluates
    both ``jnp.where`` operands).

    The regularization terms of the gradient and of every Hessian matvec
    read the carried ``Lap^2`` images (``Iterate``).  The spectral
    preconditioner's image is exact and free (``_lap2_of_precond``); a
    custom factory's output is transformed once per application.
    """
    v, lap2 = it
    interp = interp or _interp_fn(cfg, prob.grid)
    grid = prob.grid
    with telemetry.annotate("gn.gradient"):
        state = obj.newton_state(v, prob, ops, interp, lap2=lap2)
        gnorm = jnp.sqrt(grid.norm_sq(state.g))

    # ---- Newton step: PCG on H dv = -g with (beta Lap^2)^{-1} preconditioner
    def matvec(p):
        hess = obj.gn_hessian_matvec if cfg.gauss_newton else obj.full_hessian_matvec
        return hess(p.v, state, prob, ops, interp, lap2=p.lap2)

    def spectral_precond(r):
        # single coalesced ride pair: P (beta Lap^2)^{-1} r
        return ops.precond_project(r, prob.beta, prob.incompressible)

    if precond is None:
        precond = spectral_precond

        def image(r, z):
            return _lap2_of_precond(r, prob.beta)
    else:
        precond = precond(state, prob)

        def image(r, z):
            return ops.biharmonic(z)

    # Critical-accumulation guard: the PCG recursion runs in >= f32 even when
    # ``field_dtype`` stores fields in bf16.  Casting the rhs and the
    # preconditioner output (z0 seeds p0) up to ``ct`` keeps x/r/p/rz wide for
    # the whole while_loop — JAX promotion then absorbs any bf16 matvec output
    # into f32 updates — while matvec/precond internals keep the cheap
    # storage dtype for their transform rides.
    ct = jnp.promote_types(v.dtype, jnp.float32)
    base_precond = precond

    def wide_precond(r):
        z = base_precond(r).astype(ct)
        return Iterate(z, image(r, z).astype(ct))

    with telemetry.annotate("gn.pcg"):
        eta = jnp.minimum(cfg.eta_max, jnp.sqrt(gnorm / jnp.maximum(g0_forcing, 1e-30)))
        rhs = -state.g
        if prob.incompressible:
            rhs = ops.leray(rhs)
        rhs = rhs.astype(ct)
        sol = pcg(matvec, rhs, wide_precond, grid.inner, eta, cfg.max_cg)
        dv, ldv = sol.x
        if prob.incompressible:
            dv = ops.leray(dv).astype(ct)

        gdv = grid.inner(state.g, dv)
        # fall back to steepest descent if PCG returned a non-descent direction
        dv = jnp.where(gdv < 0, dv, -spectral_precond(state.g).astype(ct))
        ldv = jnp.where(gdv < 0, ldv, -_lap2_of_precond(state.g, prob.beta).astype(ct))
        gdv = jnp.minimum(gdv, grid.inner(state.g, dv))

    # ---- Armijo backtracking on J
    def j_of(vv):
        jval, (_, _, _, plan) = obj.evaluate_objective(vv, prob, ops, interp)
        return jval, _halo_need(plan, vv, grid)

    def ls_cond(c):
        alpha, jnew, it = c
        armijo = jnew <= state.j_val + cfg.armijo_c1 * alpha * gdv
        return jnp.logical_and(~armijo, it < cfg.max_line_search)

    def ls_body(c):
        alpha, _, it = c
        alpha = alpha * 0.5
        return (alpha, j_of(v + alpha * dv)[0], it + 1)

    with telemetry.annotate("gn.line_search"):
        alpha0 = jnp.float32(1.0)
        j1, need1 = j_of(v + alpha0 * dv)
        alpha, j_new, ls_it = jax.lax.while_loop(ls_cond, ls_body, (alpha0, j1, jnp.int32(0)))

    with telemetry.annotate("gn.update"):
        accepted = j_new < state.j_val
        v_new = jnp.where(accepted, v + alpha * dv, v)

        # in-graph health guard: classify the step (NaN/Inf, divergence, PCG
        # breakdown) and revert a non-finite iterate to the last good one
        status = health.classify(
            v_in=v,
            v_out=v_new,
            j_val=state.j_val,
            j_new=j_new,
            gnorm=gnorm,
            pcg_x=sol.x.v,
            pcg_rel=sol.rel_res,
            accepted=accepted,
        )
        lap2_new = jnp.where(accepted, lap2 + alpha * ldv, lap2)
        it_new = Iterate(health.freeze(v_new, v, status),
                         health.freeze(lap2_new, lap2, status))

        log = NewtonLog(
            j_val=state.j_val,
            misfit=state.misfit,
            reg=state.reg,
            gnorm=gnorm,
            cg_iters=sol.iters,
            step_len=jnp.where(accepted, alpha, 0.0),
            ls_iters=ls_it,
            status=status,
            halo_need=jnp.maximum(_halo_need(state.plan, v, grid), need1),
        )
    return it_new, log


def solve(
    rho_R: jnp.ndarray,
    rho_T: jnp.ndarray,
    grid: Grid,
    cfg: GNConfig,
    ops: SpectralOps | None = None,
    v0: jnp.ndarray | None = None,
    verbose: bool = False,
    callback: Callable[[int, dict], None] | None = None,
    interp=None,
    precond=None,
    g0_ref: float | None = None,
):
    """Full registration drive: (optional) beta continuation + Newton loop.

    The per-iteration work is jit-compiled once per (grid, beta); the Python
    loop handles convergence, logging, and checkpoint callbacks.  On a mesh,
    pass ``ops=ctx.ops, interp=ctx.interp`` from a ``DistContext``.

    ``precond`` is the factory forwarded to ``newton_iteration``.  ``g0_ref``
    overrides the reference gradient norm of the CONVERGENCE test only: the
    multilevel driver passes the *cold-start* fine-grid norm so a warm-started
    level terminates at the same absolute tolerance a single-level solve
    would, instead of chasing gtol relative to its already-small gradient.
    The Eisenstat-Walker FORCING reference is decoupled from it (see
    ``newton_iteration``): each beta stage forces against its own first
    gradient norm (first call uses a tiny sentinel, i.e. ``eta = eta_max``),
    so warm stages keep loose inner solves rather than inheriting the tight
    ``gnorm/g0_ref`` ratio and over-solving PCG.

    Without ``interp`` the solver builds its own executor, whose Pallas
    ghost budget follows the grid and ``n_t`` (``kops.ghost_budget``).  An
    iteration whose departure points reach beyond it (``NewtonLog.halo_need``)
    is run again from the same iterate with a widened budget (the gather
    past ``kops.MAX_HALO``), so the solve follows the path of an unbounded
    interpolation.  A caller's ``interp`` keeps its own budget policy.
    """
    cfg = _tuned_cfg(cfg, grid, ops)
    ops = ops or SpectralOps(grid, field_dtype=cfg.field_dtype)
    v = v0 if v0 is not None else jnp.zeros((3,) + grid.shape, grid.dtype)
    cur = with_lap2(v, ops, cold=v0 is None)
    own_interp = interp is None
    interp = interp or _interp_fn(cfg, grid)

    betas = tuple(cfg.beta_continuation) + (cfg.beta,)
    history: list[dict] = []
    total_matvecs = 0
    total_newton = 0
    # static per-application cost of a multigrid precond (0.0 for spectral)
    pc_cost = float(getattr(precond, "fine_equiv_cost", 0.0))
    total_precond_fe = 0.0
    status_code = health.OK

    for beta in betas:
        prob = obj.Problem(
            grid=grid,
            rho_R=rho_R,
            rho_T=rho_T,
            beta=float(beta),
            n_t=cfg.n_t,
            incompressible=cfg.incompressible,
        )
        # the images are arguments, not closed-over constants: baked into
        # the program they would make every image pair a new compilation
        def make_step(interp, prob=prob):
            def step(it_, g_, rho_R_, rho_T_):
                return newton_iteration(
                    it_, g_, prob._replace(rho_R=rho_R_, rho_T=rho_T_),
                    ops=ops, cfg=cfg, interp=interp, precond=precond,
                )

            return jax.jit(step)

        step_fn = make_step(interp)
        # convergence reference: g0_ref if supplied, else this stage's first
        # gradient norm; forcing reference: ALWAYS the stage's first gradient
        # norm (sentinel 1e-30 on the first call -> eta = eta_max).
        g0 = None if g0_ref is None else jnp.float32(g0_ref)
        g_forcing = None
        it = 0
        while it < cfg.max_newton:
            with telemetry.span("gn.newton_iter", beta=float(beta), iter=it) as sp:
                g_f = g_forcing if g_forcing is not None else jnp.float32(1e-30)
                nxt, log = sp.sync(step_fn(cur, g_f, rho_R, rho_T))
            wider = own_interp and interp.widened(float(log.halo_need), grid.shape)
            if wider:  # the budget cut this step short: run it again, wider
                interp, step_fn = wider, make_step(wider)
                continue
            cur = nxt
            if g_forcing is None:
                g_forcing = log.gnorm
            if g0 is None:
                g0 = log.gnorm
            total_matvecs += int(log.cg_iters)
            total_newton += 1
            total_precond_fe += (int(log.cg_iters) + 1) * pc_cost
            status_code = int(log.status)
            rec = {
                "beta": float(beta),
                "iter": it,
                "J": float(log.j_val),
                "misfit": float(log.misfit),
                "reg": float(log.reg),
                "gnorm": float(log.gnorm),
                "rel_gnorm": float(log.gnorm / max(float(g0), 1e-30)),
                "cg_iters": int(log.cg_iters),
                "step": float(log.step_len),
                "armijo_trials": int(log.ls_iters),
                "status": health.status_name(status_code),
            }
            history.append(rec)
            if callback:
                callback(it, rec)
            # the single console sink renders this exactly as the old
            # verbose print did; a JSONL sink gets the typed record
            telemetry.emit(
                telemetry.NewtonIterEvent(
                    source="gn.solve",
                    beta=rec["beta"],
                    iter=it,
                    j_val=rec["J"],
                    misfit=rec["misfit"],
                    reg=rec["reg"],
                    gnorm=rec["gnorm"],
                    rel_gnorm=rec["rel_gnorm"],
                    cg_iters=rec["cg_iters"],
                    step_len=rec["step"],
                    armijo_trials=rec["armijo_trials"],
                    wall_s=sp.wall_s,
                    level=rec.get("level"),
                ),
                echo=verbose,
            )
            if health.is_failure(status_code):
                # a NaN-poisoned / diverging / broken-down solve will not
                # heal by iterating further: stop the stage, surface the
                # reason, and let the caller's retry policy take over
                telemetry.counter(
                    "resilience.guard_tripped", status=rec["status"], source="gn.solve"
                )
                break
            if rec["rel_gnorm"] <= cfg.gtol or rec["step"] == 0.0:
                break
            it += 1
        if health.is_failure(status_code):
            break

    # final status of the last beta stage (host maps convergence/iteration
    # cap onto the codes the in-graph guard cannot decide)
    if history and health.is_failure(status_code):
        final_status = history[-1]["status"]
    elif history and history[-1]["rel_gnorm"] <= cfg.gtol:
        final_status = health.status_name(health.CONVERGED)
    elif history and history[-1]["step"] == 0.0:
        final_status = health.status_name(health.STAGNATED)
    else:
        final_status = health.status_name(health.MAX_NEWTON)

    telemetry.emit(
        telemetry.SolveEvent(
            source="gn.solve",
            newton_iters=total_newton,
            hessian_matvecs=total_matvecs,
            fine_equiv_matvecs=float(total_matvecs),
            precond_fine_equiv_matvecs=total_precond_fe,
            compiled_executables=None,
        )
    )
    return {
        "v": cur.v,
        # the carried Lap^2 v: what the solver's gradient reads; a transform
        # of v's f32 values is noisier
        "lap2_v": cur.lap2,
        "history": history,
        "newton_iters": total_newton,
        "hessian_matvecs": total_matvecs,
        "precond_fine_equiv_matvecs": total_precond_fe,
        "status": final_status,
    }


# ---------------------------------------------------------------------------
# Cohort-parallel solver: a subjects axis S through the whole GN iteration
# ---------------------------------------------------------------------------


def newton_iteration_cohort(
    it: Iterate,
    g0_forcing: jnp.ndarray,
    active: jnp.ndarray,
    prob: obj.Problem,
    ops: SpectralOps,
    cfg: GNConfig,
    interp=None,
):
    """One masked Gauss-Newton step for a cohort ``Iterate`` (``v`` and its
    ``Lap^2`` image, both ``(S, 3, N..)``).

    Structurally ``newton_iteration`` with every scalar recursion made
    per-subject ``(S,)``: Eisenstat-Walker forcing, PCG termination
    (``pcg_masked``), the descent safeguard, and Armijo backtracking all
    mask on ``active`` so a converged/rejected subject freezes (zero step,
    velocity unchanged) without perturbing the others — the live subjects'
    trajectories match independent single solves up to batched-transform
    roundoff.  All S subjects share every transport/interp/transform ride,
    which is the whole point: one ghost exchange and one coalesced FFT pair
    serve the entire cohort (docstring of ``solve_cohort``).

    ``active`` gates cost too: an all-False cohort still traces one program
    but ``pcg_masked``/line-search loops exit immediately, so retired
    subjects accrue no Hessian matvecs in the ``(S,)`` ``cg_iters`` meter.
    """
    v, lap2 = it
    interp = interp or _interp_fn(cfg, prob.grid)
    grid = prob.grid
    with telemetry.annotate("gn.gradient"):
        state = obj.newton_state(v, prob, ops, interp, lap2=lap2)
        gnorm = jnp.sqrt(grid.norm_sq_per(state.g))

    def bc(s):  # (S,) -> (S,1,1,1,1)
        return s.reshape(s.shape + (1,) * (v.ndim - 1))

    def matvec(p):
        return obj.gn_hessian_matvec(p.v, state, prob, ops, interp, lap2=p.lap2)

    def spectral_precond(r):
        return ops.precond_project(r, prob.beta, prob.incompressible)

    # >= f32 PCG recursion guard — same rationale as ``newton_iteration``
    ct = jnp.promote_types(v.dtype, jnp.float32)

    def wide_precond(r):
        z = spectral_precond(r).astype(ct)
        return Iterate(z, _lap2_of_precond(r, prob.beta).astype(ct))

    with telemetry.annotate("gn.pcg"):
        eta = jnp.minimum(cfg.eta_max, jnp.sqrt(gnorm / jnp.maximum(g0_forcing, 1e-30)))
        rhs = -state.g
        if prob.incompressible:
            rhs = ops.leray(rhs)
        rhs = rhs.astype(ct)
        sol = pcg_masked(matvec, rhs, wide_precond, grid.inner_per, eta, cfg.max_cg, active)
        dv, ldv = sol.x
        if prob.incompressible:
            dv = ops.leray(dv).astype(ct)

        # per-subject steepest-descent safeguard
        gdv = grid.inner_per(state.g, dv)
        dv = jnp.where(bc(gdv < 0), dv, -spectral_precond(state.g).astype(ct))
        ldv = jnp.where(bc(gdv < 0), ldv, -_lap2_of_precond(state.g, prob.beta).astype(ct))
        gdv = jnp.minimum(gdv, grid.inner_per(state.g, dv))

    def j_of(vv):
        jval, _ = obj.evaluate_objective(vv, prob, ops, interp)
        return jval  # (S,)

    # lockstep per-subject Armijo: each halving step shares one objective
    # evaluation (one forward transport for the whole cohort); subjects that
    # already satisfy the condition freeze their (alpha, j_new).
    def ls_cond(c):
        alpha, jnew, it = c
        armijo = jnew <= state.j_val + cfg.armijo_c1 * alpha * gdv
        return jnp.logical_and(jnp.any(active & ~armijo), it < cfg.max_line_search)

    def ls_body(c):
        alpha, jnew, it = c
        armijo = jnew <= state.j_val + cfg.armijo_c1 * alpha * gdv
        halve = active & ~armijo
        alpha = jnp.where(halve, alpha * 0.5, alpha)
        jtrial = j_of(v + bc(alpha) * dv)
        jnew = jnp.where(halve, jtrial, jnew)
        return (alpha, jnew, it + 1)

    with telemetry.annotate("gn.line_search"):
        alpha0 = jnp.ones((v.shape[0],), jnp.float32)
        j1 = j_of(v + bc(alpha0) * dv)
        alpha, j_new, ls_it = jax.lax.while_loop(ls_cond, ls_body, (alpha0, j1, jnp.int32(0)))

    with telemetry.annotate("gn.update"):
        accepted = active & (j_new < state.j_val)
        v_new = jnp.where(bc(accepted), v + bc(alpha) * dv, v)
        lap2_new = jnp.where(bc(accepted), lap2 + bc(alpha) * ldv, lap2)

        # per-subject in-graph health guard: the reductions keep the subjects
        # axis, and a sick subject's iterate is frozen so its NaNs never feed
        # the cohort's shared transform rides on the next step
        status = health.classify(
            v_in=v,
            v_out=v_new,
            j_val=state.j_val,
            j_new=j_new,
            gnorm=gnorm,
            pcg_x=sol.x.v,
            pcg_rel=sol.rel_res,
            accepted=accepted,
            active=active,
            axes=tuple(range(1, v.ndim)),
        )
        it_new = Iterate(health.freeze(v_new, v, status),
                         health.freeze(lap2_new, lap2, status))

        log = NewtonLog(
            j_val=state.j_val,
            misfit=state.misfit,
            reg=state.reg,
            gnorm=gnorm,
            cg_iters=sol.iters,
            step_len=jnp.where(accepted, alpha, 0.0),
            ls_iters=ls_it,  # shared lockstep halvings (scalar, not per-subject)
            status=status,
        )
    return it_new, log


def _cohort_step(
    it: Iterate,
    g0_forcing: jnp.ndarray,
    active: jnp.ndarray,
    beta: jnp.ndarray,
    rho_R: jnp.ndarray,
    rho_T: jnp.ndarray,
    *,
    grid: Grid,
    cfg: GNConfig,
    ops: SpectralOps,
    interp,
):
    """Jit body for one cohort Newton iteration with EVERYTHING that varies
    across a serving session traced: ``beta`` (continuation stage), the image
    stacks (slot refills swap subjects without recompiling), the per-subject
    forcing references and the active mask.  ``beta`` flows traced through
    ``Problem`` into the spectral scale factories, which accept traced
    scalars — so one (grid, mesh, cfg) bucket compiles exactly ONE
    executable for its whole lifetime (pinned by ``tests/test_cohort.py``).
    """
    prob = obj.Problem(
        grid=grid,
        rho_R=rho_R,
        rho_T=rho_T,
        beta=beta,
        n_t=cfg.n_t,
        incompressible=cfg.incompressible,
    )
    return newton_iteration_cohort(it, g0_forcing, active, prob, ops, cfg, interp)


def make_cohort_step(grid: Grid, cfg: GNConfig, ops: SpectralOps | None = None, interp=None):
    """Build the shared jitted cohort step for a (grid, mesh, cfg) bucket.

    The returned function has signature
    ``step_fn(it, g0_forcing, active, beta, rho_R, rho_T)`` (``it`` an
    ``Iterate``) and is what
    ``solve_cohort`` iterates and what ``launch/reg_serve.py`` keeps hot in
    its executable cache across job admissions.
    """
    if not cfg.gauss_newton:
        raise NotImplementedError(
            "cohort solves support the Gauss-Newton Hessian only (cfg.gauss_newton=True)"
        )
    cfg = _tuned_cfg(cfg, grid, ops)
    ops = ops or SpectralOps(grid, field_dtype=cfg.field_dtype)
    interp = interp or _interp_fn(cfg, grid)
    return jax.jit(partial(_cohort_step, grid=grid, cfg=cfg, ops=ops, interp=interp))


def solve_cohort(
    rho_R: jnp.ndarray,
    rho_T: jnp.ndarray,
    grid: Grid,
    cfg: GNConfig,
    ops: SpectralOps | None = None,
    v0: jnp.ndarray | None = None,
    verbose: bool = False,
    callback: Callable[[int, dict], None] | None = None,
    interp=None,
    g0_ref: float | None = None,
    active: jnp.ndarray | None = None,
    step_fn=None,
):
    """Register S subjects at once: ``rho_R``/``rho_T`` are ``(S, N..)``.

    The cohort axis amortizes the fixed cost of a distributed solve — the
    collective latency of each ghost exchange / pencil all-to-all and the
    per-call dispatch overhead — across S independent registrations that
    ride the SAME batched kernels (counted-collective pin: an S=4 cohort
    issues strictly fewer all-to-alls than 4 single solves).  Per-subject
    masking keeps the numerics faithful: each subject follows its own
    Eisenstat-Walker forcing, PCG termination, Armijo schedule, and
    termination test, and a converged subject retires (frozen velocity,
    zero further matvec cost) while the rest continue.

    ``active`` optionally deactivates subjects from the start (a serving
    front end admits a partially-filled cohort).  ``step_fn`` optionally
    supplies a pre-built ``make_cohort_step`` executable so many cohorts
    share one compilation (the reg_serve bucket cache); its static config
    must match ``(grid, cfg)``.

    Returns per-subject lists for ``newton_iters``/``hessian_matvecs``/
    ``fine_equiv_matvecs`` (single-level: fine-equivalent == raw matvecs)
    and ``compiled_executables`` — the jit cache size of ``step_fn``, which
    the one-executable acceptance test pins to 1 across a full
    continuation schedule.
    """
    if not cfg.gauss_newton:
        raise NotImplementedError(
            "cohort solves support the Gauss-Newton Hessian only (cfg.gauss_newton=True)"
        )
    S = rho_R.shape[0]
    v = v0 if v0 is not None else jnp.zeros((S, 3) + grid.shape, grid.dtype)
    v = with_lap2(v, ops or SpectralOps(grid), cold=v0 is None)
    if step_fn is None:
        step_fn = make_cohort_step(grid, cfg, ops=ops, interp=interp)
    active0 = (
        jnp.ones((S,), bool) if active is None else jnp.asarray(active, bool)
    )

    betas = tuple(cfg.beta_continuation) + (cfg.beta,)
    history: list[dict] = []
    newton_counts = np.zeros(S, np.int64)
    cg_counts = np.zeros(S, np.int64)
    status_codes = np.zeros(S, np.int64)

    for beta in betas:
        stage_act = active0
        # every stage re-activates its subjects; final statuses are the
        # final stage's retirement reasons
        status_codes[np.asarray(active0)] = health.OK
        g0 = None if g0_ref is None else jnp.full((S,), g0_ref, jnp.float32)
        g_forcing = jnp.full((S,), 1e-30, jnp.float32)
        have_forcing = False
        for it in range(cfg.max_newton):
            act_np = np.asarray(stage_act)
            if not act_np.any():
                break
            with telemetry.span("gn.cohort_iter", beta=float(beta), iter=it) as sp:
                v, log = sp.sync(
                    step_fn(v, g_forcing, stage_act, jnp.float32(beta), rho_R, rho_T)
                )
            if not have_forcing:
                g_forcing = log.gnorm
                have_forcing = True
            if g0 is None:
                g0 = log.gnorm
            newton_counts += act_np
            cg_counts += np.asarray(log.cg_iters, np.int64)
            rel = np.asarray(log.gnorm) / np.maximum(np.asarray(g0), 1e-30)
            step = np.asarray(log.step_len)
            code = np.asarray(log.status, np.int64)
            failed = act_np & np.isin(code, health.FAILED_CODES)
            done = act_np & ((rel <= cfg.gtol) | (step == 0.0) | failed)
            stage_act = jnp.asarray(act_np & ~done)
            # retirement-reason bookkeeping (host decides converged/stagnated;
            # the in-graph guard decides the failure modes)
            status_codes[failed] = code[failed]
            conv = done & ~failed & (rel <= cfg.gtol)
            status_codes[conv] = health.CONVERGED
            stag = done & ~failed & ~conv
            status_codes[stag] = np.where(
                code[stag] == health.OK, health.STAGNATED, code[stag]
            )
            if failed.any():
                telemetry.counter(
                    "resilience.guard_tripped",
                    value=int(failed.sum()),
                    source="gn.solve_cohort",
                )
            rec = {
                "beta": float(beta),
                "iter": it,
                "J": [float(x) for x in np.asarray(log.j_val)],
                "misfit": [float(x) for x in np.asarray(log.misfit)],
                "reg": [float(x) for x in np.asarray(log.reg)],
                "gnorm": [float(x) for x in np.asarray(log.gnorm)],
                "rel_gnorm": [float(x) for x in rel],
                "cg_iters": [int(x) for x in np.asarray(log.cg_iters)],
                "step": [float(x) for x in step],
                "active": [bool(x) for x in act_np],
                "armijo_trials": int(log.ls_iters),
                "status": [int(x) for x in code],
            }
            history.append(rec)
            if callback:
                callback(it, rec)
            telemetry.emit(
                telemetry.NewtonIterEvent(
                    source="gn.solve_cohort",
                    beta=rec["beta"],
                    iter=it,
                    j_val=rec["J"],
                    misfit=rec["misfit"],
                    reg=rec["reg"],
                    gnorm=rec["gnorm"],
                    rel_gnorm=rec["rel_gnorm"],
                    cg_iters=rec["cg_iters"],
                    step_len=rec["step"],
                    armijo_trials=rec["armijo_trials"],
                    wall_s=sp.wall_s,
                    subjects=S,
                    active=rec["active"],
                ),
                echo=verbose,
            )

    # subjects still live after the final stage exhausted max_newton
    act0_np = np.asarray(active0)
    status_codes[act0_np & (status_codes == health.OK)] = health.MAX_NEWTON

    out = {
        "v": v.v,
        "history": history,
        "newton_iters": [int(x) for x in newton_counts],
        "hessian_matvecs": [int(x) for x in cg_counts],
        # single-level cohort: every matvec is a fine-grid matvec
        "fine_equiv_matvecs": [float(x) for x in cg_counts],
        "active": [bool(x) for x in np.asarray(active0)],
        "compiled_executables": int(step_fn._cache_size()),
        "status": [health.status_name(c) for c in status_codes],
    }
    telemetry.emit(
        telemetry.SolveEvent(
            source="gn.solve_cohort",
            newton_iters=out["newton_iters"],
            hessian_matvecs=out["hessian_matvecs"],
            fine_equiv_matvecs=out["fine_equiv_matvecs"],
            compiled_executables=out["compiled_executables"],
        )
    )
    return out
