#!/usr/bin/env python3
"""Bring-up smoke: run the registration main path on a TPU and check it.

    python3 chip_smoke.py              # one chip: phases A, B and C
    python3 chip_smoke.py --chips 4    # the 2x2 pencil-mesh path only

One chip (``jax.devices()[0]``):

  A  ``register()`` on the paper's synthetic problem at 128^3 (beta 1e-2,
     n_t 4, gtol 1e-2): converged, ``det(grad y) > 0``, residual below 1.
     At the solution the objective and the gradient are evaluated on the
     chip and, as the reference, in float64 on the host CPU: they agree to
     a relative 1e-3 (the gradient relative to its data term, see
     ``cpu_agreement``), and the solver's carried ``Lap^2 v`` agrees with
     ``v`` itself.
  B  the planned Pallas tricubic apply at 128^3, C=1 and C=3, compiled for
     the chip and compared with the jnp gather ``ref.interp_apply`` (run on
     the host CPU).
  C  ``serve_jobs``: 4 jobs on 2 cohort slots at 128^3; every job must
     converge on its first attempt (a retry counts as a failure).

Four chips (one process drives all four through ``DistContext``):

  a  128^3 on the 2x2 mesh against the one-chip solve: the same PCG
     iterations (the last Newton iteration's within one: its inner solve
     starts after the gradient has met gtol, and a 1e-7 relative change of
     the input moves it by one), and ``|v_mesh - v_local| / |v_local|``
     within 1e-4 or within 10x of the one-chip solve's own spread under
     that change, whichever is larger;
  b  256^3 (``claire-256``) on the 2x2 mesh, checked by its own
     convergence, ``det(grad y) > 0`` and residual.

A failed check, an exception or any ``halo_budget_exceeded`` event makes
the script exit non-zero.  Without a TPU it exits non-zero and prints no
result.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

N_LOCAL = 128
N_MESH = 256
BETA, N_T, GTOL = 1e-2, 4, 1e-2
CPU_RTOL = 1e-3
CARRY_RTOL = 1e-4
MESH_RTOL = 1e-4
# (a) bar against roundoff: the solve amplifies a 1e-7 relative change of
# its input ~1000x, and two f32 FFT backends differ by such changes at
# every transform (CPU, mesh vs one device: 1.7x the perturbation spread
# at 32^3, 0.63x at 64^3 — 1.07e-4, over a flat 1e-4)
PERTURB, MESH_SPREAD = 1e-7, 10.0
PALLAS_ATOL = 1e-4
SERVE_JOBS, SERVE_SLOTS = 4, 2


class CompileClock:
    """Seconds XLA spends compiling (jax.monitoring); tracing and lowering,
    whose events nest, are left in the run time."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


class Phase:
    """Times one phase (wall, compile share) and records its failure."""

    def __init__(self, smoke, name):
        self.smoke, self.name = smoke, name

    def __enter__(self):
        self.c0, self.t0 = self.smoke.clock.total, time.perf_counter()
        self.smoke.say(self.name, "start")
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self.t0
        comp = self.smoke.clock.total - self.c0
        self.smoke.report(self.name, compile_s=comp, run_s=wall - comp,
                          peak_bytes_in_use=self.smoke.peak_bytes())
        if exc is not None:
            traceback.print_exception(exc_type, exc, tb)
            self.smoke.fail(self.name, f"{exc_type.__name__}: {exc}")
        return True  # later phases still run; the verdict is failed


class Smoke:
    def __init__(self, device):
        self.device = device
        self.t0 = time.perf_counter()
        self.clock = CompileClock()
        self.failures: list[str] = []

    def say(self, phase, text):
        print(f"{time.perf_counter() - self.t0:8.1f}s [{phase}] {text}", flush=True)

    def report(self, phase, **fields):
        self.say(phase, json.dumps(fields, default=str))

    def fail(self, phase, why):
        self.say(phase, f"FAIL: {why}")
        self.failures.append(f"{phase}: {why}")

    def check(self, phase, ok, what):
        self.say(phase, f"{'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(f"{phase}: {what}")

    def peak_bytes(self):
        stats = self.device.memory_stats() or {}
        return stats.get("peak_bytes_in_use")


def solver_config():
    from repro.core import gauss_newton as gn

    # autotune="off": a machine-local tuning cache must not change the knobs
    return gn.GNConfig(beta=BETA, n_t=N_T, gtol=GTOL, autotune="off")


def check_solve(smoke, phase, out):
    last = out["history"][-1]
    smoke.report(phase, status=out["status"], newton_iters=out["newton_iters"],
                 pcg_iters=[h["cg_iters"] for h in out["history"]],
                 rel_gnorm=last["rel_gnorm"], det_min=out["det_min"],
                 det_max=out["det_max"], residual_rel=out["residual_rel"])
    smoke.check(phase, out["status"] == "converged", f"status {out['status']!r}")
    smoke.check(phase, last["rel_gnorm"] <= GTOL, f"rel_gnorm {last['rel_gnorm']} <= {GTOL}")
    smoke.check(phase, out["det_min"] > 0, f"det_min {out['det_min']} > 0")
    smoke.check(phase, out["residual_rel"] < 1, f"residual_rel {out['residual_rel']} < 1")


def local_solve(smoke, phase, perturb=0.0):
    """The one-chip 128^3 registration (phase A; the reference of phase a).
    ``perturb``: a relative change of that size, seeded noise, added to the
    reference image first."""
    import jax
    import jax.numpy as jnp

    from repro.core.registration import RegistrationConfig, register
    from repro.data.synthetic import synthetic_problem
    from repro.kernels import ops as kops

    cfg = solver_config()
    smoke.report(phase, grid=[N_LOCAL] * 3, perturb=perturb,
                 interp=kops.resolve(cfg.interp_method, (N_LOCAL,) * 3)._asdict(),
                 knobs={k: getattr(cfg, k) for k in
                        ("interp_method", "plan_dtype", "field_dtype", "autotune")})
    rho_R, rho_T, _, grid = synthetic_problem(N_LOCAL, n_t=N_T)
    if perturb:
        noise = jax.random.normal(jax.random.PRNGKey(0), rho_R.shape, rho_R.dtype)
        rho_R = rho_R + perturb * jnp.max(jnp.abs(rho_R)) * noise
    out = register(rho_R, rho_T, RegistrationConfig(solver=cfg), grid=grid, verbose=True)
    jax.block_until_ready(out["v"])
    check_solve(smoke, phase, out)
    return out, rho_R, rho_T, grid


def halo(n):
    """The ghost budget the solver derives for an n^3 grid (8 at 128^3, 16
    at 256^3): the synthetic velocity moves up to N / (2 pi n_t) voxels."""
    from repro.kernels import ops as kops

    return kops.ghost_budget((n,) * 3, N_T)


def data_terms(v, rho_R, rho_T, grid, method):
    """The terms of J(v) and of the reduced gradient ``g = beta Lap^2 v +
    P b`` that are computed from ``v`` on the presmoothed pair, as the
    solver evaluates them (compressible: ``P`` is the identity): J, the
    state rho(1), the adjoint lambda(0), the grad-rho series and P b."""
    import jax.numpy as jnp

    from repro.core import objective as obj
    from repro.core.spectral import SpectralOps
    from repro.kernels import ops as kops

    ops = SpectralOps(grid)
    prob = obj.Problem(grid=grid, rho_R=ops.smooth(rho_R), rho_T=ops.smooth(rho_T),
                       beta=BETA, n_t=N_T, incompressible=False)
    st = obj.newton_state(v, prob, ops, kops.make_interp(method, halo=halo(grid.shape[0])),
                          lap2=jnp.zeros_like(v))
    return {"J": st.j_val, "rho(1)": st.rho_series[-1], "lambda(0)": st.lam_series[0],
            "grad rho": st.grad_rho_series, "P b": st.g}


def carried_image_error(v, lap2):
    """``|Lap^-2 lap2 - (v - mean v)| / |v - mean v|`` in float64 on the
    host: the inverse damps the f32 roundoff of both by ``|k|^-4``, so this
    checks the solver's carried image against ``v`` itself, independently
    of how the solver updated it.  (``Lap^2`` applied to ``v``'s f32
    values instead amplifies their roundoff by ``|k|^4``: 34% of the image
    at 128^3 on the CPU.)"""
    import numpy as np

    n = v.shape[-3:]
    k2 = sum(np.meshgrid(*(np.fft.fftfreq(m, 1.0 / m) ** 2 for m in n), indexing="ij"))
    inv = np.divide(1.0, k2**2, out=np.zeros_like(k2), where=k2 > 0)
    back = np.fft.ifftn(np.fft.fftn(lap2, axes=(-3, -2, -1)) * inv, axes=(-3, -2, -1)).real
    v0 = v - v.mean(axis=(-3, -2, -1), keepdims=True)
    return float(np.linalg.norm(back - v0) / np.linalg.norm(v0))


def cpu_agreement(smoke, phase, out, rho_R, rho_T, grid):
    """J and g at the solution on the chip against a float64 evaluation on
    the host CPU (the gather, from ``v`` alone), and the same f32
    evaluation on the CPU beside it.

    ``g = beta Lap^2 v + P b`` is a difference of two terms each ~400x
    larger than ``g`` at the solution, and f32 ``P b`` carries ~6e-6 of
    its size in roundoff (the spectral ``grad rho`` amplifies rho's f32
    rounding by ``|k|``): relative to ``|g|`` an f32 gradient is only good
    to ~2e-3, on the CPU too.  So the chip's gradient is checked relative
    to its data term ``|P b|``, what the chip computes from ``v`` here, and
    ``beta Lap^2 v`` — the solver's carried image, the same on both sides —
    is checked against ``v`` by ``carried_image_error``.  Both relative
    errors against ``|g|`` are printed."""
    import functools

    import jax
    import numpy as np

    from repro.core.grid import make_grid

    def host(x):
        return {k: np.asarray(a, np.float64) for k, a in x.items()}

    args = (out["v"], rho_R, rho_T)
    chip = host(jax.jit(functools.partial(data_terms, grid=grid, method="auto"))(*args))
    smoke.say(phase, "objective and gradient terms evaluated on the chip")
    cpu = jax.devices("cpu")[0]
    on_cpu = [np.asarray(a) for a in args]
    f32 = host(jax.jit(functools.partial(data_terms, grid=grid, method="ref"))(
        *jax.device_put(on_cpu, cpu)))
    with jax.enable_x64(True):
        g64 = make_grid(grid.shape, dtype=np.float64)
        ref = host(jax.jit(functools.partial(data_terms, grid=g64, method="ref"))(
            *jax.device_put([a.astype(np.float64) for a in on_cpu], cpu)))
    smoke.say(phase, "objective and gradient terms evaluated on the host CPU")

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    smoke.report(phase, terms={k: {"chip": rel(chip[k], ref[k]), "cpu_f32": rel(f32[k], ref[k])}
                               for k in ref})
    lap2 = np.asarray(out["lap2_v"], np.float64)
    reg = BETA * lap2
    g = reg + ref["P b"]
    dg = np.linalg.norm(chip["P b"] - ref["P b"])
    rel_j, rel_g = rel(chip["J"], ref["J"]), float(dg / np.linalg.norm(ref["P b"]))
    carry = carried_image_error(np.asarray(out["v"], np.float64), lap2)
    smoke.report(phase, J_chip=float(chip["J"]), J_ref=float(ref["J"]), rel_J=rel_j,
                 norm_g=float(np.linalg.norm(g)), norm_Pb=float(np.linalg.norm(ref["P b"])),
                 norm_beta_lap2=float(np.linalg.norm(reg)), grad_diff_over_Pb=rel_g,
                 grad_diff_over_g=float(dg / np.linalg.norm(g)),
                 cpu_f32_grad_diff_over_g=rel(f32["P b"] + reg, g), carried_image_error=carry)
    smoke.check(phase, rel_j <= CPU_RTOL, f"objective chip vs cpu {rel_j:.3e} <= {CPU_RTOL}")
    smoke.check(phase, rel_g <= CPU_RTOL,
                f"gradient chip vs cpu {rel_g:.3e} (of |P b|) <= {CPU_RTOL}")
    smoke.check(phase, carry <= CARRY_RTOL,
                f"carried Lap^2 v vs v {carry:.3e} <= {CARRY_RTOL}")


def phase_a(smoke):
    with Phase(smoke, "A"):
        out, rho_R, rho_T, grid = local_solve(smoke, "A")
        cpu_agreement(smoke, "A", out, rho_R, rho_T, grid)


def phase_b(smoke):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.planner import departure_displacement
    from repro.core.grid import make_grid
    from repro.data.synthetic import paper_velocity
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.kernels.tricubic import tricubic_apply_pallas

    shape, budget = (N_LOCAL,) * 3, halo(N_LOCAL)
    rs = kops.resolve("pallas", shape)
    cpu = jax.devices("cpu")[0]
    grid = make_grid(shape)
    # one semi-Lagrangian step of the paper's velocity: a smooth departure
    # field of up to ~5 voxels, as the solver's transports see it
    plan = ref.make_interp_plan(departure_displacement(paper_velocity(grid), grid, 1.0 / N_T))
    key = jax.random.PRNGKey(0)
    for c in (1, 3):
        with Phase(smoke, f"B.C{c}"):
            smoke.report(f"B.C{c}", interp=rs._asdict(), halo=budget,
                         halo_need=float(plan.halo_need))
            f = jax.random.normal(jax.random.fold_in(key, c), (c,) + shape, jnp.float32)
            t = time.perf_counter()
            kernel = jax.jit(functools.partial(
                tricubic_apply_pallas, tile=rs.tile, halo=budget, interpret=rs.interpret,
            )).lower(f, plan).compile()
            compile_s = time.perf_counter() - t
            got = jax.block_until_ready(kernel(f, plan))
            t = time.perf_counter()
            for _ in range(5):
                jax.block_until_ready(kernel(f, plan))
            per_call = (time.perf_counter() - t) / 5
            # the reference gather runs on the host CPU: on the TPU the XLA
            # gather takes minutes to compile at this size
            expect = jax.jit(ref.interp_apply)(*jax.device_put((f, plan), cpu))
            err = float(np.max(np.abs(np.asarray(got) - np.asarray(expect))))
            smoke.report(f"B.C{c}", kernel_compile_s=compile_s, pallas_s_per_call=per_call,
                         max_abs_err=err, finite=bool(np.isfinite(np.asarray(got)).all()))
            smoke.check(f"B.C{c}", err <= PALLAS_ATOL, f"max |pallas - gather| {err:.3e} <= {PALLAS_ATOL}")


def phase_c(smoke):
    import numpy as np

    from repro.data.synthetic import synthetic_problem
    from repro.launch.reg_serve import RegJob, serve_jobs
    from repro.resilience.policy import RetryPolicy

    with Phase(smoke, "C"):
        rng = np.random.default_rng(0)
        jobs = []
        for j in range(SERVE_JOBS):
            amp = float(rng.uniform(0.3, 1.0))
            rho_R, rho_T, _, _ = synthetic_problem(N_LOCAL, n_t=N_T, amplitude=amp)
            jobs.append(RegJob(job_id=f"job{j}", rho_R=rho_R, rho_T=rho_T))
        # a retry policy is armed so that a job which needed it shows up as
        # attempts > 1 instead of failing outright
        out = serve_jobs(jobs, solver_config(), slots=SERVE_SLOTS, retry=RetryPolicy())
        res = sorted(out["results"], key=lambda r: r.job_id)
        smoke.report("C", buckets={str(k): v for k, v in out["buckets"].items()},
                     compiled_executables=out["compiled_executables"],
                     jobs=[{"job": r.job_id, "status": r.status, "attempts": r.attempts,
                            "newton_iters": r.newton_iters,
                            "pcg_iters": r.hessian_matvecs, "rel_gnorm": r.rel_gnorm}
                           for r in res])
        smoke.check("C", len(res) == SERVE_JOBS, f"{len(res)} of {SERVE_JOBS} jobs returned")
        for r in res:
            smoke.check("C", r.status == "converged" and r.attempts == 1,
                        f"{r.job_id}: status {r.status!r}, attempts {r.attempts}")


def phase_mesh(smoke):
    import jax
    import numpy as np

    from repro.core.registration import RegistrationConfig, register
    from repro.data.synthetic import synthetic_problem
    from repro.dist.context import DistContext
    from repro.core.grid import make_grid
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    cfg = solver_config()

    def mesh_solve(phase, rho_R, rho_T, n):
        ctx = DistContext(make_grid((n,) * 3), mesh, halo=halo(n), autotune="off")
        smoke.report(phase, grid=[n] * 3, mesh=dict(mesh.shape), halo=ctx.halo,
                     interp=ctx.interp.kernel._asdict(), chunk=ctx.chunk,
                     plan_dtype=ctx.plan_dtype, field_dtype=ctx.field_dtype)
        out = register(ctx.shard_scalar(rho_R), ctx.shard_scalar(rho_T),
                       RegistrationConfig(solver=cfg), grid=ctx.grid, ctx=ctx, verbose=True)
        jax.block_until_ready(out["v"])
        check_solve(smoke, phase, out)
        return out, ctx

    def pcg_iters(out):
        return [h["cg_iters"] for h in out["history"]]

    def rel_v(out, ref):
        v = np.asarray(ref["v"], np.float64)
        return float(np.linalg.norm(np.asarray(out["v"], np.float64) - v) / np.linalg.norm(v))

    with Phase(smoke, "a.local"):
        local, rho_R, rho_T, _ = local_solve(smoke, "a.local")
    # the roundoff spread of the one-chip solve itself: the same solve with
    # its reference image changed by a relative PERTURB
    with Phase(smoke, "a.spread"):
        moved, _, _, _ = local_solve(smoke, "a.spread", perturb=PERTURB)
        spread = rel_v(moved, local)
        smoke.report("a.spread", pcg_iters=pcg_iters(moved), rel_v=spread)
    with Phase(smoke, "a.mesh"):
        meshed, _ = mesh_solve("a.mesh", rho_R, rho_T, N_LOCAL)
        rel, bar = rel_v(meshed, local), max(MESH_RTOL, MESH_SPREAD * spread)
        cg_l, cg_m = pcg_iters(local), pcg_iters(meshed)
        smoke.report("a.mesh", pcg_iters_local=cg_l, pcg_iters_mesh=cg_m, rel_v=rel,
                     spread=spread, bar=bar)
        smoke.check("a.mesh", len(cg_l) == len(cg_m) and cg_l[:-1] == cg_m[:-1]
                    and abs(cg_l[-1] - cg_m[-1]) <= 1,
                    "same PCG iterations as the one-chip solve (the last within one)")
        smoke.check("a.mesh", rel <= bar, f"|v_mesh - v_local| / |v_local| {rel:.3e} <= {bar:.3e}"
                    f" (max of {MESH_RTOL} and {MESH_SPREAD:g}x the spread {spread:.3e})")

    with Phase(smoke, "b.mesh256"):
        ctx = DistContext(make_grid((N_MESH,) * 3), mesh, halo=halo(N_MESH), autotune="off")
        rho_R, rho_T, _, _ = synthetic_problem(N_MESH, n_t=N_T, ctx=ctx)
        mesh_solve("b.mesh256", rho_R, rho_T, N_MESH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 pencil-mesh path and its reference")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} device(s)",
              file=sys.stderr)
        return 2

    from repro import telemetry

    smoke = Smoke(dev)
    smoke.report("setup", platform=dev.platform, kind=dev.device_kind,
                 devices=len(devices), compile_cache=cache_dir,
                 libtpu_init_args=os.environ.get("LIBTPU_INIT_ARGS", ""))
    t0 = time.perf_counter()
    phases = (phase_mesh,) if args.chips == 4 else (phase_a, phase_b, phase_c)
    for phase in phases:
        try:
            phase(smoke)
        except Exception as e:  # a phase body outside its own Phase guard
            traceback.print_exc()
            smoke.fail(phase.__name__, f"{type(e).__name__}: {e}")
    jax.effects_barrier()  # flush halo-budget callbacks before counting them
    overflows = telemetry.counters().get("halo_budget_exceeded", 0)
    smoke.check("end", overflows == 0, f"{overflows:g} halo_budget_exceeded events")
    smoke.report("end", wall_s=time.perf_counter() - t0, compile_s=smoke.clock.total,
                 peak_bytes_in_use=smoke.peak_bytes())
    if smoke.failures:
        print("chip_smoke: FAILED\n  " + "\n  ".join(smoke.failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
