"""Cohort-parallel registration (gn.solve_cohort + launch.reg_serve).

Acceptance pins:
* an S=4 cohort matches 4 independent ``gn.solve`` runs — per-subject
  velocities within fp tolerance AND identical Newton/PCG iteration counts
  (the masked per-subject recursions reproduce independent trajectories);
* per-subject masked termination retires early-convergers without
  perturbing the rest;
* ONE compiled executable serves a whole continuation schedule / serve
  session (beta, image stacks, active mask are traced);
* on the 2x4 mesh, one cohort Newton program issues the same all-to-all
  count as one single-subject program — strictly fewer than 4 single
  solves' worth (slow/dist, via subprocess).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from conftest import run_multidevice as _run  # noqa: E402

from repro.core import gauss_newton as gn  # noqa: E402
from repro.data.synthetic import synthetic_problem  # noqa: E402

AMPS = (0.2, 0.6, 1.0, 1.4)  # spread convergence speeds across the cohort
CFG = gn.GNConfig(beta=1e-2, n_t=2, max_newton=8, gtol=1e-2, max_cg=20)


@pytest.fixture(scope="module")
def cohort_and_singles():
    probs = [synthetic_problem(12, n_t=2, amplitude=a) for a in AMPS]
    grid = probs[0][3]
    singles = [gn.solve(rR, rT, grid, CFG) for rR, rT, _, _ in probs]
    rho_R = jnp.stack([p[0] for p in probs])
    rho_T = jnp.stack([p[1] for p in probs])
    cohort = gn.solve_cohort(rho_R, rho_T, grid, CFG)
    return grid, rho_R, rho_T, singles, cohort


def test_cohort_matches_independent_solves(cohort_and_singles):
    _, _, _, singles, cohort = cohort_and_singles
    for s, single in enumerate(singles):
        dv = float(jnp.max(jnp.abs(cohort["v"][s] - single["v"])))
        ref = max(float(jnp.max(jnp.abs(single["v"]))), 1e-30)
        assert dv / ref < 5e-4, (s, dv / ref)
        # identical masked trajectories: same Newton count, same PCG billing
        assert cohort["newton_iters"][s] == single["newton_iters"], s
        assert cohort["hessian_matvecs"][s] == single["hessian_matvecs"], s


def test_masked_termination_retires_early_convergers(cohort_and_singles):
    _, _, _, singles, cohort = cohort_and_singles
    iters = cohort["newton_iters"]
    # the amplitude spread guarantees a genuine early retirement
    assert min(iters) < max(iters), iters
    # a retired subject stops accruing matvecs: every iteration after its
    # retirement logs 0 cg_iters and 0 step for it
    for s in range(len(iters)):
        post = [rec for rec in cohort["history"] if rec["iter"] >= iters[s]]
        assert all(rec["cg_iters"][s] == 0 for rec in post), s
        assert all(not rec["active"][s] for rec in post), s


def test_single_executable_across_continuation(cohort_and_singles):
    grid, rho_R, rho_T, _, cohort = cohort_and_singles
    assert cohort["compiled_executables"] == 1
    # a full continuation schedule (two betas) still compiles ONE program:
    # beta is a traced argument all the way through the spectral scales
    cfg = gn.GNConfig(beta=1e-3, beta_continuation=(1e-2,), n_t=2,
                      max_newton=3, gtol=1e-2, max_cg=10)
    res = gn.solve_cohort(rho_R, rho_T, grid, cfg)
    assert res["compiled_executables"] == 1


def test_inactive_subjects_are_frozen_and_free(cohort_and_singles):
    grid, rho_R, rho_T, singles, _ = cohort_and_singles
    active = jnp.asarray([True, False, True, False])
    res = gn.solve_cohort(rho_R, rho_T, grid, CFG, active=active)
    for s in (1, 3):  # never-active: zero velocity, zero billing
        assert float(jnp.max(jnp.abs(res["v"][s]))) == 0.0
        assert res["newton_iters"][s] == 0
        assert res["hessian_matvecs"][s] == 0
    for s in (0, 2):  # live subjects unperturbed by the frozen ones
        dv = float(jnp.max(jnp.abs(res["v"][s] - singles[s]["v"])))
        ref = max(float(jnp.max(jnp.abs(singles[s]["v"]))), 1e-30)
        assert dv / ref < 5e-4, s
        assert res["newton_iters"][s] == singles[s]["newton_iters"]


def test_serve_refill_one_executable(cohort_and_singles):
    from repro.launch.reg_serve import CohortServer, RegJob

    grid, rho_R, rho_T, singles, _ = cohort_and_singles
    server = CohortServer(grid, CFG, slots=2)
    server.admit(*(RegJob(job_id=s, rho_R=rho_R[s], rho_T=rho_T[s])
                   for s in range(rho_R.shape[0])))
    results = {r.job_id: r for r in server.run()}
    assert len(results) == 4
    # slot refills never recompile: one executable for the whole session
    assert server.compiled_executables() == 1
    for s, single in enumerate(singles):
        r = results[s]
        assert r.converged, s
        # per-subject billing matches the job's own independent solve
        assert r.newton_iters == single["newton_iters"], s
        assert r.hessian_matvecs == single["hessian_matvecs"], s
        dv = float(np.max(np.abs(r.v - np.asarray(single["v"]))))
        ref = max(float(jnp.max(jnp.abs(single["v"]))), 1e-30)
        assert dv / ref < 5e-4, s


def test_result_carries_lap2_on_the_device(cohort_and_singles):
    """A retired job's ``JobResult.lap2``, as ``step`` returns it, is its
    slot's carried ``Lap^2 v``, left on the device: what the velocity's own
    transform gives, up to f32 roundoff."""
    from repro.core.spectral import SpectralOps
    from repro.launch.reg_serve import CohortServer, RegJob

    grid, rho_R, rho_T, _, _ = cohort_and_singles
    server = CohortServer(grid, CFG, slots=2)
    server.admit(*(RegJob(job_id=s, rho_R=rho_R[s], rho_T=rho_T[s]) for s in range(2)))
    results = []
    while server.queue or server.active.any():
        results += server.step()
    assert len(results) == 2
    ops = SpectralOps(grid)
    for r in results:
        assert isinstance(r.lap2, jax.Array) and r.lap2.shape == r.v.shape
        want = np.asarray(ops.biharmonic(jnp.asarray(r.v)))
        err = np.max(np.abs(np.asarray(r.lap2) - want)) / np.max(np.abs(want))
        assert err < 1e-3, (r.job_id, err)


def test_server_device_memory_does_not_grow_with_retired_jobs(cohort_and_singles):
    """The server's own record of its retired jobs holds no ``Lap^2 v`` on
    the device: a second drain of the same work leaves at most the bytes of
    its results' host ``v`` live (which a CPU backend shares with the
    device buffer it was read from; a chip copies it out)."""
    import gc

    from repro.launch.reg_serve import CohortServer, RegJob

    grid, rho_R, rho_T, _, _ = cohort_and_singles
    server = CohortServer(grid, CFG, slots=2)
    n = rho_R.shape[0]

    def drain_and_count():
        server.admit(*(RegJob(job_id=s, rho_R=rho_R[s], rho_T=rho_T[s]) for s in range(n)))
        server.run()
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays())

    first = drain_and_count()
    grown = drain_and_count() - first
    assert len(server.results) == 2 * n
    assert all(r.lap2 is None for r in server.results)
    assert grown <= sum(r.v.nbytes for r in server.results[n:]), grown


def test_retirement_status_splits_converged_from_max_newton(cohort_and_singles):
    """Regression for the JobResult.converged=False conflation: the explicit
    ``status`` field distinguishes a clean convergence from an iteration-cap
    exit (and JobEvent carries the same reason)."""
    from repro.launch.reg_serve import CohortServer, RegJob
    from repro import telemetry

    grid, rho_R, rho_T, _, _ = cohort_and_singles
    # iteration cap too small to converge the hardest subject
    capped = gn.GNConfig(beta=1e-2, n_t=2, max_newton=1, gtol=1e-6, max_cg=20)
    server = CohortServer(grid, capped, slots=2)
    server.admit(RegJob(job_id="hard", rho_R=rho_R[3], rho_T=rho_T[3]))
    with telemetry.ListSink() as sink:
        res = server.run()[0]
    assert not res.converged and res.status == "max_newton"
    assert res.attempts == 1
    job_recs = [r for r in sink.records if r["kind"] == "job"]
    assert job_recs[0]["status"] == "max_newton"

    server2 = CohortServer(grid, CFG, slots=2)
    server2.admit(RegJob(job_id="easy", rho_R=rho_R[0], rho_T=rho_T[0]))
    res2 = server2.run()[0]
    assert res2.converged and res2.status == "converged"


def test_restore_snapshot_without_carried_image(cohort_and_singles):
    """A snapshot written before the solver carried ``Lap^2 v`` (no "lap2"
    leaf) resumes: the image is transformed from the slots' ``v`` once,
    and the resumed jobs finish as from a full snapshot."""
    from repro.launch.reg_serve import CohortServer, RegJob

    grid, rho_R, rho_T, _, _ = cohort_and_singles
    server = CohortServer(grid, CFG, slots=2)
    server.admit(*(RegJob(job_id=s, rho_R=rho_R[s], rho_T=rho_T[s]) for s in (2, 3)))
    server.step()
    server.step()
    tree, meta = server.snapshot()
    old = {k: v for k, v in tree.items() if k != "lap2"}
    full = CohortServer.restore(grid, CFG, tree, meta, step_fn=server.step_fn)
    legacy = CohortServer.restore(grid, CFG, old, meta, step_fn=server.step_fn)
    lap2 = np.asarray(tree["lap2"])
    assert np.linalg.norm(np.asarray(legacy._lap2) - lap2) < 1e-3 * np.linalg.norm(lap2)
    want = {r.job_id: r for r in full.run()}
    got = {r.job_id: r for r in legacy.run()}
    for s in (2, 3):
        assert got[s].status == want[s].status == "converged", s
        assert got[s].newton_iters == want[s].newton_iters, s


def test_server_rejects_continuation():
    from repro.launch.reg_serve import CohortServer

    grid = synthetic_problem(12, n_t=2)[3]
    cfg = gn.GNConfig(beta_continuation=(1e-1,), n_t=2)
    with pytest.raises(ValueError):
        CohortServer(grid, cfg, slots=2)


def test_cohort_requires_gauss_newton():
    grid = synthetic_problem(12, n_t=2)[3]
    cfg = gn.GNConfig(n_t=2, gauss_newton=False)
    with pytest.raises(NotImplementedError):
        gn.make_cohort_step(grid, cfg)


# --------------------------------------------------------------------------- #
# distributed: the collective-amortization claim, counted in compiled HLO
# --------------------------------------------------------------------------- #
@pytest.mark.slow
@pytest.mark.dist
def test_cohort_collectives_beat_independent_solves_on_mesh():
    """One S=4 cohort Newton program on the 2x4 mesh issues the SAME
    all-to-all/ppermute count as one single-subject program — i.e. strictly
    fewer collectives than the 4 programs of 4 independent solves — and its
    velocities match the local cohort."""
    _run(
        """
        from functools import partial
        from repro.core import objective as obj, gauss_newton as gn
        from repro.core.spectral import SpectralOps
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        from repro.data.synthetic import synthetic_problem

        probs = [synthetic_problem(16, n_t=2, amplitude=a) for a in (0.4, 0.7, 0.9, 1.0)]
        grid = probs[0][3]
        rho_R = jnp.stack([p[0] for p in probs])
        rho_T = jnp.stack([p[1] for p in probs])
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = DistContext(grid, mesh, halo=4)
        cfg = gn.GNConfig(n_t=2, max_cg=10)

        def count(txt, op):
            return sum(1 for l in txt.splitlines() if op in l and "=" in l)

        prob_1 = obj.Problem(grid, ctx.shard_scalar(probs[0][0]),
                             ctx.shard_scalar(probs[0][1]), 1e-2, 2, False)
        single = jax.jit(partial(gn.newton_iteration, prob=prob_1, ops=ctx.ops,
                                 cfg=cfg, interp=ctx.interp))
        v1 = gn.with_lap2(jnp.zeros((3,) + grid.shape, jnp.float32), ctx.ops, cold=True)
        txt1 = single.lower(jax.tree.map(ctx.shard_vector, v1),
                            jnp.float32(1)).compile().as_text()

        prob_c = obj.Problem(grid, rho_R, rho_T, 1e-2, 2, False)
        coh = jax.jit(partial(gn.newton_iteration_cohort, prob=prob_c, ops=ctx.ops,
                              cfg=cfg, interp=ctx.interp))
        vc = gn.with_lap2(jnp.zeros((4, 3) + grid.shape, jnp.float32), ctx.ops, cold=True)
        gf = jnp.full((4,), 1e-30, jnp.float32)
        act = jnp.ones((4,), bool)
        lowered = coh.lower(vc, gf, act)
        txt4 = lowered.compile().as_text()

        for op in ("all-to-all", "collective-permute"):
            c1, c4 = count(txt1, op), count(txt4, op)
            # the cohort program's collective count is independent of S: the
            # S=4 program stays under TWO single programs' worth (vs the 4x
            # of 4 independent solves) — the whole exchange/transform stack
            # rides once per call regardless of cohort size
            assert c4 < 2 * c1, (op, c1, c4)

        # numerics: mesh cohort step == local cohort step
        local = SpectralOps(grid)
        prob_l = obj.Problem(grid, rho_R, rho_T, 1e-2, 2, False)
        vl, ll = jax.jit(partial(gn.newton_iteration_cohort, prob=prob_l,
                                 ops=local, cfg=cfg))(vc, gf, act)
        vd, ld = coh(vc, gf, act)
        assert float(jnp.max(jnp.abs(vl.v - vd.v))) < 1e-4
        assert np.array_equal(np.asarray(ll.cg_iters), np.asarray(ld.cg_iters))
        """
    )
