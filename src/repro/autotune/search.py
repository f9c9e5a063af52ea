"""Coordinate-descent knob sweep over the distributed Hessian matvec.

``sweep_cell(grid, mesh, beta=...)`` is the driver behind
``python -m benchmarks.run --suite autotune`` and ``GNConfig(autotune=
"sweep")``: for one ``(grid, mesh, beta)`` cell it

1. builds a deterministic synthetic registration problem (smooth cosine
   blobs — no RNG, so counted sweeps are bit-reproducible),
2. scores candidate knob sets on the compiled ``gn_hessian_matvec``
   program — the inner-loop kernel that dominates a solve (paper Table V
   bills everything in its units) — via ``repro.autotune.measure``
   (median wall seconds on real devices, deterministic collective
   count/byte cost on CPU hosts),
3. walks the knobs in a fixed order (chunk, field_dtype, plan_dtype,
   interp_method), keeping each knob's winner before sweeping the next —
   coordinate descent, |candidates| programs per knob instead of the
   cross product,
4. optionally races preconditioner variants (spectral vs two-level) on a
   short *solve* — the matvec program can't see a preconditioner, so this
   knob is scored by the deterministic ``hessian_matvecs +
   precond_fine_equiv_matvecs`` meter (or solve wall time),
5. writes the winner to the ``TuningCache`` so the next
   ``DistContext``/``gn.solve`` of the same cell resolves it without
   re-sweeping (pinned by ``tests/test_autotune.py``).

Wall-mode winners must beat the incumbent by ``HYSTERESIS`` (5%) —
machine noise should not flip a knob off its default; counted mode is
deterministic and takes any strict improvement.

Heavy imports (jax, repro.core, repro.dist) happen inside functions: this
module is imported by ``repro.autotune`` which core modules consult lazily.
"""
from __future__ import annotations

import dataclasses

from repro import telemetry
from repro.autotune import measure
from repro.autotune.cache import TunedConfig, TuningCache, cell_key

HYSTERESIS = 0.05  # wall mode: >5% improvement required to leave a default
KNOB_ORDER = ("chunk", "field_dtype", "plan_dtype", "interp_method")


def default_candidates(mode: str, backend: str | None = None) -> dict:
    """Per-knob candidate lists.  ``None`` always means "consumer default".

    Counted mode skips ``interp_method``: kernel choice never changes the
    collective structure, so the cost model cannot rank it (ties keep the
    default).  ``pallas`` only enters on TPU where it can actually win.
    """
    cands = {
        "chunk": [None, 1, 2, 4, "auto"],
        "field_dtype": [None, "bfloat16"],
        "plan_dtype": [None, "bfloat16"],
    }
    if mode == "wall":
        cands["interp_method"] = [None, "pallas"] if backend == "tpu" else [None]
    else:
        cands["interp_method"] = [None]
    return cands


def _synthetic_pair(grid):
    """Deterministic smooth reference/template pair (no RNG)."""
    import jax.numpy as jnp
    import numpy as np

    axes = [np.linspace(0.0, 2 * np.pi, n, endpoint=False) for n in grid.shape]
    X, Y, Z = np.meshgrid(*axes, indexing="ij")
    rho_R = np.exp(np.cos(X) + 0.5 * np.cos(Y) - 0.3 * np.cos(Z)) / np.e
    rho_T = np.exp(np.cos(X - 0.4) + 0.5 * np.cos(Y + 0.3) - 0.3 * np.cos(Z - 0.2)) / np.e
    return (
        jnp.asarray(rho_R, grid.dtype),
        jnp.asarray(rho_T, grid.dtype),
    )


def _test_velocity(grid):
    import jax.numpy as jnp
    import numpy as np

    axes = [np.linspace(0.0, 2 * np.pi, n, endpoint=False) for n in grid.shape]
    X, Y, Z = np.meshgrid(*axes, indexing="ij")
    v = np.stack(
        [0.05 * np.sin(X) * np.cos(Y), 0.05 * np.sin(Y) * np.cos(Z), 0.04 * np.sin(Z)]
    )
    return jnp.asarray(v, grid.dtype)


def _build_ctx(grid, mesh, knobs: dict, *, axes=("data", "model"), halo: int = 4):
    from repro.dist.context import DistContext

    return DistContext(
        grid,
        mesh,
        axes=axes,
        halo=halo,
        chunk=knobs.get("chunk"),
        interp_method=knobs.get("interp_method") or "auto",
        plan_dtype=knobs.get("plan_dtype"),
        field_dtype=knobs.get("field_dtype"),
        autotune="off",  # the sweep must not consult the cache it is filling
    )


def _matvec_score(grid, mesh, beta, knobs, *, axes, halo, mode, repeats) -> float:
    """Cost of the compiled Hessian matvec under one candidate knob set."""
    import jax

    from repro.core import objective as obj

    ctx = _build_ctx(grid, mesh, knobs, axes=axes, halo=halo)
    rho_R, rho_T = _synthetic_pair(grid)
    prob = obj.Problem(
        grid=grid,
        rho_R=ctx.shard_scalar(rho_R),
        rho_T=ctx.shard_scalar(rho_T),
        beta=float(beta),
        n_t=2,
        incompressible=False,
    )
    v = ctx.shard_vector(_test_velocity(grid))
    state = obj.newton_state(v, prob, ctx.ops, ctx.interp)
    f = jax.jit(lambda p: obj.gn_hessian_matvec(p, state, prob, ctx.ops, ctx.interp))
    p = ctx.shard_vector(_test_velocity(grid))
    if mode == "counted":
        return measure.counted_cost(f.lower(p))
    return measure.wall_cost(f, p, repeats=repeats)


def _precond_score(grid, mesh, beta, knobs, variant, *, axes, halo, mode, repeats) -> float:
    """Race a preconditioner variant on a short solve.

    The matvec program cannot see the preconditioner, so this knob uses the
    solver's own deterministic billing meter: raw Hessian matvecs plus the
    fine-grid-equivalent cost of every preconditioner application
    (``gn.solve``'s Table-V accounting).  Wall mode times the solve.
    """
    import time

    from repro.core import gauss_newton as gn

    ctx = _build_ctx(grid, mesh, knobs, axes=axes, halo=halo)
    rho_R, rho_T = _synthetic_pair(grid)
    cfg = gn.GNConfig(beta=float(beta), n_t=2, max_newton=2, max_cg=8, autotune="off")
    precond = None
    if variant == "two_level":
        from repro.core import objective as obj
        from repro.multilevel import precond as mlp

        coarse_shape = tuple(n // 2 for n in grid.shape)
        coarse_ctx = ctx.coarsen(coarse_shape)
        prob = obj.Problem(
            grid=grid,
            rho_R=ctx.shard_scalar(rho_R),
            rho_T=ctx.shard_scalar(rho_T),
            beta=float(beta),
            n_t=2,
            incompressible=False,
        )
        precond = mlp.make_two_level_precond(
            prob, ctx.ops, coarse_ctx.ops, interp_coarse=coarse_ctx.interp, galerkin=True
        )
    t0 = time.perf_counter()
    out = gn.solve(
        ctx.shard_scalar(rho_R),
        ctx.shard_scalar(rho_T),
        grid,
        cfg,
        ops=ctx.ops,
        interp=ctx.interp,
        precond=precond,
    )
    wall = time.perf_counter() - t0
    if mode == "counted":
        return float(out["hessian_matvecs"]) + float(out["precond_fine_equiv_matvecs"])
    return wall


def sweep_cell(
    grid,
    mesh,
    *,
    beta: float = 1e-2,
    axes=("data", "model"),
    halo: int = 4,
    cache: TuningCache | None = None,
    mode: str | None = None,
    candidates: dict | None = None,
    include_precond: bool = True,
    repeats: int = 3,
    write: bool = True,
) -> dict:
    """Sweep one ``(grid, mesh, beta)`` cell; returns the full record
    (candidates, per-candidate costs, winner) and persists the winner."""
    import jax

    mode = mode or measure.measure_mode()
    cands = candidates if candidates is not None else default_candidates(
        mode, jax.default_backend()
    )
    cache = cache or TuningCache()
    ndev = int(mesh.devices.size)
    cell = cell_key(grid.shape, ndev, beta)

    best: dict = {}
    trials = []
    with telemetry.span("autotune.sweep_cell", cell=cell, mode=mode):
        base_cost = _matvec_score(
            grid, mesh, beta, best, axes=axes, halo=halo, mode=mode, repeats=repeats
        )
        trials.append({"knobs": dict(best), "cost": base_cost})
        for knob in KNOB_ORDER:
            incumbent = best.get(knob)
            incumbent_cost = base_cost
            for cand in cands.get(knob, [None]):
                if cand == incumbent:
                    continue
                trial = dict(best)
                trial[knob] = cand
                try:
                    cost = _matvec_score(
                        grid, mesh, beta, trial,
                        axes=axes, halo=halo, mode=mode, repeats=repeats,
                    )
                except Exception as e:  # infeasible candidate (divisibility, ...)
                    telemetry.counter(
                        "autotune.candidate_failed", knob=knob, value=1.0, error=str(e)[:120]
                    )
                    continue
                trials.append({"knobs": dict(trial), "cost": cost})
                margin = HYSTERESIS if mode == "wall" and incumbent is None else 0.0
                if cost < incumbent_cost * (1.0 - margin):
                    incumbent, incumbent_cost = cand, cost
            if incumbent is not None:
                best[knob] = incumbent
            base_cost = incumbent_cost

        precond_winner = None
        precond_trials = []
        if include_precond:
            for variant in ("spectral", "two_level"):
                try:
                    cost = _precond_score(
                        grid, mesh, beta, best, variant,
                        axes=axes, halo=halo, mode=mode, repeats=repeats,
                    )
                except Exception as e:
                    telemetry.counter(
                        "autotune.candidate_failed", knob="precond", error=str(e)[:120]
                    )
                    continue
                precond_trials.append({"variant": variant, "cost": cost})
            if precond_trials:
                winner = min(precond_trials, key=lambda t: t["cost"])
                margin = HYSTERESIS if mode == "wall" else 0.0
                spectral = next(
                    (t for t in precond_trials if t["variant"] == "spectral"), None
                )
                if (
                    winner["variant"] != "spectral"
                    and spectral is not None
                    and winner["cost"] >= spectral["cost"] * (1.0 - margin)
                ):
                    winner = spectral
                precond_winner = winner["variant"]

    tuned = TunedConfig(
        chunk=best.get("chunk"),
        interp_method=best.get("interp_method"),
        plan_dtype=best.get("plan_dtype"),
        field_dtype=best.get("field_dtype"),
        precond=None if precond_winner in (None, "spectral") else precond_winner,
        mode=mode,
        cost=float(base_cost),
    )
    if write:
        cache.put(cell, tuned)
    return {
        "cell": cell,
        "mode": mode,
        "grid": list(grid.shape),
        "devices": ndev,
        "beta": float(beta),
        "trials": trials,
        "precond_trials": precond_trials if include_precond else [],
        "winner": tuned.knobs(),
        "cost": float(base_cost),
        "cache_path": cache.path,
    }


def sweep_mesh_layouts(grid, devices=None, *, beta: float = 1e-2, halo: int = 4,
                       mode: str | None = None, repeats: int = 3) -> dict:
    """Race mesh layouts (1xD / 2xD/2 / Dx1) over the same device set.

    The mesh is an input of ``DistContext`` (callers own placement), so the
    winner is *recorded* for the bench tables rather than cached as a knob
    — ``BENCH_autotune.json`` carries it next to the cell winners.
    """
    import jax

    from repro.launch.mesh import make_mesh

    devices = list(devices if devices is not None else jax.devices())
    D = len(devices)
    mode = mode or measure.measure_mode()
    layouts = [(1, D), (D, 1)]
    if D % 2 == 0:
        layouts.insert(1, (2, D // 2))
    rows = []
    for p1, p2 in layouts:
        if grid.shape[0] % max(p1, 1) or grid.shape[1] % max(p2, 1):
            continue
        mesh = make_mesh((p1, p2), ("data", "model"), devices=devices)
        try:
            cost = _matvec_score(
                grid, mesh, beta, {}, axes=("data", "model"), halo=halo,
                mode=mode, repeats=repeats,
            )
        except Exception as e:
            telemetry.counter("autotune.candidate_failed", knob="mesh", error=str(e)[:120])
            continue
        rows.append({"layout": [p1, p2], "cost": float(cost)})
    winner = min(rows, key=lambda r: r["cost"])["layout"] if rows else None
    return {"mode": mode, "layouts": rows, "winner": winner}
