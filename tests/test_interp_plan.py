"""Plan-once / apply-many interpolation: InterpPlan + batched multi-field
contract (ISSUE 3 tentpole).

Covers, single-device: batched-vs-looped equivalence on the ref oracle and
the Pallas kernel (interpret mode), planned-vs-unplanned equivalence on
both, the ``kernels.ops.Interp`` executor protocol, plan construction and
reuse inside ``SLPlan``, and plan reuse across GN Hessian (PCG) matvecs.
The 8-device mesh counterparts live in ``tests/test_dist_interp.py``.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import smooth_disp, sweep_widths

from repro.core import objective as obj
from repro.core import semilag
from repro.core.grid import make_grid
from repro.core.planner import make_plan, required_halo
from repro.core.spectral import SpectralOps
from repro.data import synthetic
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.tricubic import (
    LANE_CHUNK,
    ROW_CHUNK,
    tricubic_apply_pallas,
    tricubic_displace_pallas_many,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _problem(rng, shape=(8, 12, 16), c=4, lim=3.0):
    f = jnp.asarray(rng.standard_normal((c,) + shape), jnp.float32)
    d = jnp.asarray(rng.uniform(-lim, lim, (3,) + shape), jnp.float32)
    return f, d


def _looped(f, d):
    return jnp.stack([ref.tricubic_displace(f[i], d) for i in range(f.shape[0])])


# ----------------------------------------------------------------------- #
# ref oracle
# ----------------------------------------------------------------------- #
def test_batched_matches_looped_ref(rng):
    f, d = _problem(rng)
    np.testing.assert_allclose(
        ref.tricubic_displace_many(f, d), _looped(f, d), atol=1e-4, rtol=1e-4
    )


def test_planned_matches_unplanned_ref(rng):
    f, d = _problem(rng)
    plan = ref.make_interp_plan(d)
    np.testing.assert_allclose(
        ref.interp_apply(f, plan), _looped(f, d), atol=1e-4, rtol=1e-4
    )
    # rank-3 (no channel axis) goes through the same plan
    np.testing.assert_allclose(
        ref.interp_apply(f[0], plan), _looped(f, d)[0], atol=1e-4, rtol=1e-4
    )


def test_plan_halo_need_matches_required_halo(rng):
    _, d = _problem(rng, lim=2.7)
    plan = ref.make_interp_plan(d)
    assert float(plan.halo_need) == float(jnp.ceil(jnp.max(jnp.abs(d))))


def test_plan_apply_padded_matches_global(rng):
    f, d = _problem(rng)
    lo, hi = 5, 6
    fp = jnp.pad(f, ((0, 0), (lo, hi), (lo, hi), (lo, hi)), mode="wrap")
    plan = ref.make_interp_plan(d)
    np.testing.assert_allclose(
        ref.interp_apply_padded(fp, plan, lo), ref.interp_apply(f, plan), atol=1e-5
    )


def test_plan_exact_at_grid_points(rng):
    f = jnp.asarray(rng.standard_normal((2, 8, 8, 8)), jnp.float32)
    plan = ref.make_interp_plan(jnp.zeros((3, 8, 8, 8)))
    np.testing.assert_array_equal(ref.interp_apply(f, plan), f)


# ----------------------------------------------------------------------- #
# Pallas kernel (interpret mode on CPU)
# ----------------------------------------------------------------------- #
@pytest.mark.parametrize("c", [1, 3])
def test_pallas_batched_matches_ref(rng, c):
    shape, tile, halo = (16, 16, 32), (8, 8, 16), 4
    f, d = _problem(rng, shape, c=c, lim=halo - 0.1)
    out = tricubic_displace_pallas_many(f, d, tile=tile, halo=halo, interpret=True)
    np.testing.assert_allclose(out, _looped(f, d), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("halo,amps,edge,c", [
    pytest.param(4, None, False, 3, id="4-uniform-C3"),
    # smooth fields at the solver's budgets (row and lane sweeps not whole
    # chunks), and fields reaching the rim of the kernel's window
    pytest.param(8, (2.0, 1.5, 3.3), False, 2, id="8-smooth-C2"),
    pytest.param(8, (10.4, 10.4, 10.4), True, 1, id="8-edge-C1"),
    pytest.param(16, (3.0, 2.0, 7.0), False, 1, id="16-smooth-C1"),
    pytest.param(16, (20.8, 20.8, 20.8), True, 2, id="16-edge-C2"),
])
def test_pallas_planned_matches_ref(rng, halo, amps, edge, c):
    if amps is None:
        shape, tile = (16, 16, 32), (8, 8, 16)
        f, d = _problem(rng, shape, c=c, lim=halo - 0.1)
    else:
        # T3 is half the staged row, as on the chip, and the row does not
        # repeat with period T3: each lane half is read for what it holds
        shape, tile = (16, 8, 128), (8, 8, 64)
        d = smooth_disp(shape, amps, edge=halo if edge else None)
        assert np.any(sweep_widths(d, tile, 2) % LANE_CHUNK)
        assert np.any(sweep_widths(d, tile, 1) % ROW_CHUNK)
        assert (np.max(np.abs(d)) == halo) == edge
        f = jnp.asarray(rng.standard_normal((c,) + shape), jnp.float32)
        d = jnp.asarray(d)
    plan = ref.make_interp_plan(d)
    out = tricubic_apply_pallas(f, plan, tile=tile, halo=halo, interpret=True)
    np.testing.assert_allclose(out, _looped(f, d), atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------------- #
# bf16-packed plan weights (make_interp_plan(dtype=...), ROADMAP follow-up)
# ----------------------------------------------------------------------- #
def test_plan_bf16_packing_parity(rng):
    """Packing w to bf16 halves the plan's weight storage; the apply still
    contracts in f32 (output dtype unchanged, error at bf16 rounding level,
    far below the tricubic discretization error)."""
    f, d = _problem(rng)
    p32 = ref.make_interp_plan(d)
    pb = ref.make_interp_plan(d, dtype=jnp.bfloat16)
    assert pb.w.dtype == jnp.bfloat16
    assert pb.ib.dtype == jnp.int32 and pb.halo_need.dtype == jnp.float32
    np.testing.assert_array_equal(pb.ib, p32.ib)
    out32, outb = ref.interp_apply(f, p32), ref.interp_apply(f, pb)
    assert outb.dtype == f.dtype  # contraction upcasts, output stays f32
    np.testing.assert_allclose(outb, out32, atol=5e-2)
    assert float(jnp.max(jnp.abs(outb - out32))) > 0.0  # actually packed


def test_plan_bf16_executor_and_pallas(rng):
    """The flag rides the Interp executor (kernels.ops.make_interp) and the
    Pallas planned kernel (one-hot A-matrices built in f32 from bf16 w)."""
    shape, tile, halo = (16, 16, 32), (8, 8, 16), 4
    f, d = _problem(rng, shape, c=3, lim=halo - 0.1)
    expect = _looped(f, d)
    interp = kops.make_interp(method="ref", plan_dtype=jnp.bfloat16)
    plan = interp.make_plan(d)
    assert plan.w.dtype == jnp.bfloat16
    np.testing.assert_allclose(interp.apply_plan(f, plan), expect, atol=5e-2)
    out_pl = tricubic_apply_pallas(f, plan, tile=tile, halo=halo, interpret=True)
    np.testing.assert_allclose(out_pl, expect, atol=5e-2)


def test_plan_bf16_through_solver_config(gn_setup):
    """GNConfig(plan_dtype="bfloat16") threads the packing into the cached
    SLPlan operators without disturbing the transports beyond rounding."""
    from repro.core import gauss_newton as gn

    g, ops, prob, v = gn_setup
    interp = gn._interp_fn(gn.GNConfig(plan_dtype="bfloat16"), g)
    plan = make_plan(v, g, ops, 4, incompressible=False, interp=interp)
    assert plan.iplan_fwd.w.dtype == jnp.bfloat16
    assert plan.iplan_adj.w.dtype == jnp.bfloat16
    ref_series = semilag.transport_state(prob.rho_T, make_plan(v, g, ops, 4, False))
    np.testing.assert_allclose(
        semilag.transport_state(prob.rho_T, plan, interp), ref_series, atol=5e-2
    )


# ----------------------------------------------------------------------- #
# ops.Interp executor protocol
# ----------------------------------------------------------------------- #
@pytest.mark.parametrize("method", ["ref", "pallas"])
def test_interp_executor_protocol(rng, method):
    shape = (16, 16, 32)
    interp = kops.make_interp(method=method)
    f, d = _problem(rng, shape, c=3, lim=3.9)
    expect = _looped(f, d)
    np.testing.assert_allclose(interp(f, d), expect, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(interp(f[0], d), expect[0], atol=1e-4, rtol=1e-4)
    plan = interp.make_plan(d)
    np.testing.assert_allclose(interp.apply_plan(f, plan), expect, atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------------- #
# SLPlan integration: plans built once, reused everywhere
# ----------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def gn_setup():
    g = make_grid(16)
    ops = SpectralOps(g)
    rho_R, rho_T, v_star, _ = synthetic.synthetic_problem(16)
    prob = obj.Problem(g, rho_R, rho_T, 1e-2, 4, False)
    return g, ops, prob, 0.4 * v_star


def test_make_plan_builds_interp_plans(gn_setup):
    g, ops, prob, v = gn_setup
    plan = make_plan(v, g, ops, 4, incompressible=False)
    assert plan.iplan_fwd is not None and plan.iplan_adj is not None
    assert plan.iplan_fwd.ib.shape == (3,) + g.shape
    assert plan.iplan_fwd.w.shape == (3, 4) + g.shape
    # cached bound == the planner's recomputed bound
    bare = plan._replace(iplan_fwd=None, iplan_adj=None)
    assert float(required_halo(plan)) == float(required_halo(bare))


def test_transports_planned_equal_unplanned(gn_setup):
    """The planned applier path of semilag._bind is numerically the
    unplanned per-call path (same operators, cached vs rebuilt)."""
    g, ops, prob, v = gn_setup
    plan = make_plan(v, g, ops, 4, incompressible=False)
    bare = plan._replace(iplan_fwd=None, iplan_adj=None)
    rho = prob.rho_T
    np.testing.assert_allclose(
        semilag.transport_state(rho, plan),
        semilag.transport_state(rho, bare),
        atol=1e-4,
    )
    np.testing.assert_allclose(
        semilag.transport_adjoint(rho, plan),
        semilag.transport_adjoint(rho, bare),
        atol=1e-4,
    )
    np.testing.assert_allclose(
        semilag.deformation_displacement(v, plan),
        semilag.deformation_displacement(v, bare),
        atol=1e-4,
    )


def test_gn_matvec_plan_reuse(gn_setup, rng):
    """PCG Hessian matvecs through the cached InterpPlan equal the
    unplanned evaluation — plan reuse across matvecs is exact."""
    g, ops, prob, v = gn_setup
    interp = kops.make_interp(method="ref")
    state = obj.newton_state(v, prob, ops, interp)
    assert state.plan.iplan_fwd is not None  # threaded through newton_state
    state_bare = state._replace(plan=state.plan._replace(iplan_fwd=None, iplan_adj=None))
    for seed in (0, 1):
        vt = jnp.asarray(
            np.random.default_rng(seed).standard_normal((3,) + g.shape), jnp.float32
        )
        hp = obj.gn_hessian_matvec(vt, state, prob, ops, interp)
        hb = obj.gn_hessian_matvec(vt, state_bare, prob, ops, interp)
        np.testing.assert_allclose(hp, hb, atol=1e-4)


# ----------------------------------------------------------------------- #
# committed benchmark record (written by `benchmarks.run --suite interp`)
# ----------------------------------------------------------------------- #
def test_bench_interp_record():
    path = os.path.join(ROOT, "BENCH_interp.json")
    assert os.path.exists(path), "run: PYTHONPATH=src python -m benchmarks.run --suite interp"
    rec = json.load(open(path))
    # (a) batched C-field interp beats C looped calls in wall time at 64^3+
    rows = [r for r in rec["single_device"] if r["n"] >= 64]
    assert rows, rec
    for r in rows:
        assert r["batched_s"] < r["looped_s"], r
        assert r["planned_s"] < r["looped_s"], r
    # (b) counted: one ghost-exchange round per batched mesh call vs C
    mesh = rec["mesh"]
    assert mesh["collective_permutes"]["batched_c3"] == mesh["collective_permutes"]["c1"]
    assert (
        mesh["collective_permutes"]["looped_c3"]
        == 3 * mesh["collective_permutes"]["c1"]
    )


def test_bench_interp_record_bf16_and_pallas_columns():
    """ISSUE 8 satellite: the committed record carries measured bf16-plan
    and batched-Pallas columns next to the f32 planned path."""
    path = os.path.join(ROOT, "BENCH_interp.json")
    assert os.path.exists(path), "run: PYTHONPATH=src python -m benchmarks.run --suite interp"
    rec = json.load(open(path))
    for r in rec["single_device"]:
        # bf16-packed plans are measured on every row and stay within the
        # storage dtype's noise floor (~1e-2 relative)
        assert r["planned_bf16_s"] > 0.0, r
        assert r["planned_bf16_rel_err"] < 3e-2, r
    pallas_rows = [r for r in rec["single_device"] if "pallas_batched_s" in r]
    assert pallas_rows, "no Pallas rows in the committed record"
    for r in pallas_rows:
        assert r["pallas_mode"] in ("tpu", "interpret"), r
        assert r["pallas_rel_err"] < 1e-3, r


# ----------------------------------------------------------------------- #
# ghost budget of the local Pallas path
# ----------------------------------------------------------------------- #
def test_ghost_budget_is_a_cfl_bound():
    # a unit-speed velocity moves N / (2 pi n_t) voxels per step: 5.1 at
    # 128^3 and 10.2 at 256^3 with n_t 4, covered 1.5x
    assert kops.ghost_budget((128,) * 3, 4) == 8
    assert kops.ghost_budget((256,) * 3, 4) == 16
    assert kops.ghost_budget((64, 64, 256), 4) == 16  # the finest axis rules
    assert kops.ghost_budget((128,) * 3, 8) == 4  # more steps, shorter ones
    assert kops.ghost_budget((16,) * 3, 4) == 4  # the floor


def test_interp_widens_past_its_budget():
    pallas = kops.make_interp(method="pallas", halo=8)
    assert pallas.widened(8.0, (16, 16, 32)) is None  # within the budget
    assert pallas.widened(float("nan"), (16, 16, 32)) is None  # not a halo fault
    wider = pallas.widened(10.0, (16, 16, 32))  # a step of 10 voxels
    assert wider.method == "pallas" and wider.halo == 15
    with pytest.warns(UserWarning, match="widest ghost budget"):
        gather = pallas.widened(12.0, (16, 16, 32))
    assert gather.method == "ref"  # past MAX_HALO: the exact gather
    # the gather has no budget to outgrow
    assert kops.make_interp(method="ref", halo=8).widened(12.0, (16, 16, 32)) is None


def test_local_pallas_solve_reruns_a_step_past_its_budget(monkeypatch):
    """A local solve whose steps move past the Pallas ghost budget (here
    shrunk to 1 voxel so a 16^3 problem reaches past it) reruns the step
    with a widened budget: it follows the gather's path instead of meeting
    NaNs."""
    from repro import telemetry
    from repro.core import gauss_newton as gn

    rho_R, rho_T, _, grid = synthetic.synthetic_problem(16, n_t=1)
    base = dict(n_t=1, autotune="off")
    want = gn.solve(rho_R, rho_T, grid, gn.GNConfig(interp_method="ref", **base))
    monkeypatch.setattr(kops, "ghost_budget", lambda shape, n_t: 1)
    telemetry.reset_counters()
    got = gn.solve(rho_R, rho_T, grid, gn.GNConfig(interp_method="pallas", **base))
    counts = telemetry.counters()
    # the step that overflowed was counted, then run again with a wider budget
    assert counts.get("halo_budget_exceeded", 0) >= 1
    assert counts.get("interp.halo_widened", 0) >= 1
    assert np.isfinite(np.asarray(got["v"])).all()
    assert got["status"] == want["status"], (got["status"], want["status"])
    assert [h["cg_iters"] for h in got["history"]] == [h["cg_iters"] for h in want["history"]]
    rel = np.linalg.norm(np.asarray(got["v"] - want["v"])) / np.linalg.norm(np.asarray(want["v"]))
    assert rel < 1e-4, rel
