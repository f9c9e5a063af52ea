"""Distributed-path equivalence, via subprocesses with 8 placeholder
devices (XLA locks device count at first jax init, so these cannot run
in-process with the rest of the suite)."""
import pytest

from conftest import run_multidevice as _run

pytestmark = [pytest.mark.slow, pytest.mark.dist]


def test_pencil_fft_matches_local():
    _run(
        """
        from repro.core.grid import make_grid
        from repro.core.spectral import SpectralOps
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        grid = make_grid((16, 16, 32))
        ctx = DistContext(grid, mesh, halo=2)
        local = SpectralOps(grid)
        rng = np.random.default_rng(0)
        f = jnp.asarray(rng.standard_normal(grid.shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal((3,)+grid.shape), jnp.float32)
        fs, vs = ctx.shard_scalar(f), ctx.shard_vector(v)
        for name, a, b in [
            ("grad", ctx.ops.grad(fs), local.grad(f)),
            ("div", ctx.ops.div(vs), local.div(v)),
            ("leray", ctx.ops.leray(vs), local.leray(v)),
            ("invbih", ctx.ops.inv_biharmonic(fs), local.inv_biharmonic(f)),
        ]:
            err = float(jnp.max(jnp.abs(a - b)))
            assert err < 1e-3, (name, err)
        """
    )


def test_halo_interp_matches_reference():
    _run(
        """
        from repro.core.grid import make_grid
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        from repro.kernels import ref
        mesh = make_mesh((2, 4), ("data", "model"))
        grid = make_grid((16, 16, 32))
        rng = np.random.default_rng(0)
        f = jnp.asarray(rng.standard_normal(grid.shape), jnp.float32)
        for halo in (1, 4, 9):  # 9 > shard width: multi-hop exchange
            ctx = DistContext(grid, mesh, halo=halo)
            d = jnp.asarray(rng.uniform(-halo+0.01, halo-0.01, (3,)+grid.shape), jnp.float32)
            out = jax.jit(ctx.interp)(ctx.shard_scalar(f), jax.device_put(d, ctx.vector_sharding()))
            err = float(jnp.max(jnp.abs(out - ref.tricubic_displace(f, d))))
            assert err < 1e-4, (halo, err)
        """
    )


def test_distributed_gn_iteration_matches_local():
    _run(
        """
        from functools import partial
        from repro.core.grid import make_grid
        from repro.core.spectral import SpectralOps
        from repro.core import objective as obj, gauss_newton as gn
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        from repro.data import synthetic
        rho_R, rho_T, v_star, grid = synthetic.synthetic_problem(16)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = DistContext(grid, mesh, halo=4)
        local = SpectralOps(grid)
        cfg = gn.GNConfig()
        prob_l = obj.Problem(grid, rho_R, rho_T, 1e-2, 4, False)
        prob_d = obj.Problem(grid, ctx.shard_scalar(rho_R), ctx.shard_scalar(rho_T), 1e-2, 4, False)
        v0 = gn.with_lap2(jnp.zeros((3,)+grid.shape, jnp.float32), local, cold=True)
        vl, ll = jax.jit(partial(gn.newton_iteration, prob=prob_l, ops=local, cfg=cfg))(v0, jnp.float32(1))
        vd, ld = jax.jit(partial(gn.newton_iteration, prob=prob_d, ops=ctx.ops, cfg=cfg, interp=ctx.interp))(
            jax.tree.map(ctx.shard_vector, v0), jnp.float32(1))
        assert float(jnp.max(jnp.abs(vl.v - vd.v))) < 1e-4
        assert int(ll.cg_iters) == int(ld.cg_iters)
        """
    )


def test_multipod_tuple_axis_pencil():
    _run(
        """
        from functools import partial
        from repro.core.grid import make_grid
        from repro.core import objective as obj, gauss_newton as gn
        from repro.core.spectral import SpectralOps
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        from repro.data import synthetic
        rho_R, rho_T, v_star, grid = synthetic.synthetic_problem(16)
        mesh = make_mesh((2,2,2), ("pod","data","model"))
        ctx = DistContext(grid, mesh, axes=(("pod","data"),"model"), halo=4)
        local = SpectralOps(grid)
        cfg = gn.GNConfig()
        prob_d = obj.Problem(grid, ctx.shard_scalar(rho_R), ctx.shard_scalar(rho_T), 1e-2, 4, False)
        prob_l = obj.Problem(grid, rho_R, rho_T, 1e-2, 4, False)
        v0 = gn.with_lap2(jnp.zeros((3,)+grid.shape, jnp.float32), local, cold=True)
        vd, _ = jax.jit(partial(gn.newton_iteration, prob=prob_d, ops=ctx.ops, cfg=cfg, interp=ctx.interp))(
            jax.tree.map(ctx.shard_vector, v0), jnp.float32(1))
        vl, _ = jax.jit(partial(gn.newton_iteration, prob=prob_l, ops=local, cfg=cfg))(v0, jnp.float32(1))
        assert float(jnp.max(jnp.abs(vl.v - vd.v))) < 1e-4
        """
    )


def test_distributed_incompressible_gn_matches_local():
    """Leray/`ksq_d` on the PencilFFT backend: the incompressible GN
    iteration on the mesh is pinned to the local solver."""
    _run(
        """
        from functools import partial
        from repro.core.grid import make_grid
        from repro.core.spectral import SpectralOps
        from repro.core import objective as obj, gauss_newton as gn
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        from repro.data import synthetic
        rho_R, rho_T, v_star, grid = synthetic.synthetic_problem(16, incompressible=True, amplitude=0.5)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = DistContext(grid, mesh, halo=4)
        local = SpectralOps(grid)
        cfg = gn.GNConfig(incompressible=True)
        prob_l = obj.Problem(grid, rho_R, rho_T, 1e-2, 4, True)
        prob_d = obj.Problem(grid, ctx.shard_scalar(rho_R), ctx.shard_scalar(rho_T), 1e-2, 4, True)
        v0 = gn.with_lap2(jnp.zeros((3,)+grid.shape, jnp.float32), local, cold=True)
        vl, ll = jax.jit(partial(gn.newton_iteration, prob=prob_l, ops=local, cfg=cfg))(v0, jnp.float32(1))
        vd, ld = jax.jit(partial(gn.newton_iteration, prob=prob_d, ops=ctx.ops, cfg=cfg, interp=ctx.interp))(
            jax.tree.map(ctx.shard_vector, v0), jnp.float32(1))
        assert float(jnp.max(jnp.abs(vl.v - vd.v))) < 1e-4
        assert int(ll.cg_iters) == int(ld.cg_iters)
        # the step stays (discretely) divergence free on the mesh
        assert float(jnp.max(jnp.abs(ctx.ops.div(vd.v)))) < 1e-3
        """
    )


def test_register_on_mesh_matches_local():
    """``register(..., ctx=ctx)`` runs the SOLVE AND THE DIAGNOSTICS on the
    mesh backend (regression: diagnostics used to rebuild a local
    SpectralOps/default interp regardless of how the solve ran), and the
    whole result dict is pinned to the local pipeline."""
    _run(
        """
        from repro.core import gauss_newton as gn
        from repro.core.registration import RegistrationConfig, register
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        from repro.data import synthetic
        rho_R, rho_T, v_star, grid = synthetic.synthetic_problem(16, n_t=2)
        cfg = RegistrationConfig(
            solver=gn.GNConfig(beta=1e-2, n_t=2, max_newton=3, gtol=1e-2, max_cg=10))
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = DistContext(grid, mesh, halo=4)
        out_l = register(rho_R, rho_T, cfg, grid=grid)
        out_d = register(ctx.shard_scalar(rho_R), ctx.shard_scalar(rho_T), cfg,
                         grid=grid, ctx=ctx)
        for key in ("v", "displacement", "det_grad_y", "rho_deformed"):
            err = float(jnp.max(jnp.abs(out_l[key] - out_d[key])))
            assert err < 1e-3, (key, err)
        for key in ("residual_rel", "residual_rel_smoothed", "det_min", "det_max"):
            assert abs(out_l[key] - out_d[key]) < 1e-3, (key, out_l[key], out_d[key])
        assert out_l["newton_iters"] == out_d["newton_iters"]
        """
    )


def test_halo_budget_check():
    """Dynamic halo budget (ROADMAP): an overshooting displacement either
    NaN-poisons (halo_check="error") or falls back to the exact global
    gather (halo_check="gather") instead of silently reading wrapped ghosts."""
    _run(
        """
        from repro.core.grid import make_grid
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        from repro.kernels import ref
        mesh = make_mesh((2, 4), ("data", "model"))
        grid = make_grid((16, 16, 32))
        halo = 4
        rng = np.random.default_rng(0)
        f = jnp.asarray(rng.standard_normal(grid.shape), jnp.float32)
        d_ok = jnp.asarray(rng.uniform(-halo+0.01, halo-0.01, (3,)+grid.shape), jnp.float32)
        d_bad = d_ok.at[0, 0, 0, 0].set(halo + 2.5)

        ctx = DistContext(grid, mesh, halo=halo)  # default: halo_check="error"
        put = lambda c, d: (c.shard_scalar(f), jax.device_put(d, c.vector_sharding()))
        ok_out = jax.jit(ctx.interp)(*put(ctx, d_ok))
        assert float(jnp.max(jnp.abs(ok_out - ref.tricubic_displace(f, d_ok)))) < 1e-4
        assert bool(jnp.all(jnp.isnan(jax.jit(ctx.interp)(*put(ctx, d_bad)))))

        ctx_g = DistContext(grid, mesh, halo=halo, halo_check="gather")
        bad_out = jax.jit(ctx_g.interp)(*put(ctx_g, d_bad))
        assert float(jnp.max(jnp.abs(bad_out - ref.tricubic_displace(f, d_bad)))) < 1e-4
        """
    )


def test_mini_registration_dryrun_cells():
    """The registration dry-run machinery (single-level incompressible +
    multilevel ladder) end-to-end on the shrunken 8-device mesh."""
    _run(
        """
        import repro.launch.dryrun as dr
        from repro.launch import mesh as meshmod
        meshmod.make_production_mesh = lambda multi_pod=False: (
            meshmod.make_mesh((2,2,2), ("pod","data","model")) if multi_pod
            else meshmod.make_mesh((2,4), ("data","model")))
        dr.make_production_mesh = meshmod.make_production_mesh
        from repro.configs.claire_registration import RegConfig
        rcfg = RegConfig("mini-inc", (16, 16, 32), incompressible=True, halo=2)
        rec = dr.lower_registration_cell("mini-inc", False, verbose=False, rcfg=rcfg)
        assert rec["status"] == "ok", rec
        assert rec["components"]["hessian_matvec"]["hbm_bytes_per_chip"] > 0
        rcfg_ml = RegConfig("mini-ml", (16, 16, 32), halo=2,
                            levels=((8, 8, 16), (16, 16, 32)))
        rec2 = dr.lower_multilevel_cell("mini-ml", False, verbose=False, rcfg=rcfg_ml)
        assert rec2["status"] == "ok", rec2
        assert len(rec2["levels"]) == 2
        assert rec2["levels"][0]["fine_equiv_matvec_weight"] == 0.125
        assert rec2["levels"][1]["prolong_collectives"], rec2["levels"][1]
        """
    )


def test_lm_train_step_shards_and_runs():
    """Sharded smoke-model train step on a 2x2x2 pod mesh executes and
    matches the single-device loss."""
    _run(
        """
        from repro.configs import get_smoke_config
        from repro.models.common import ShardRules
        from repro.optim import adamw
        from repro.train.steps import build_model, make_train_step
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_smoke_config("qwen3-1.7b")
        model = build_model(cfg)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = ShardRules(mesh)
        params, specs = model.init(jax.random.PRNGKey(0), rules)
        flat_p, tdef = jax.tree.flatten(params)
        flat_s = jax.tree.flatten(specs, is_leaf=lambda s: isinstance(s, P))[0]
        params = tdef.unflatten([
            jax.device_put(a, NamedSharding(mesh, s)) for a, s in zip(flat_p, flat_s)])
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
        bsh = NamedSharding(mesh, P(("pod","data"), None))
        batch_sharded = {k: jax.device_put(v, bsh) for k, v in batch.items()}
        step = jax.jit(make_train_step(model, adamw.AdamWConfig()))
        opt = adamw.init_state(params)
        p2, o2, m = step(params, opt, batch_sharded)
        assert np.isfinite(float(m["loss"]))

        # single-device comparison
        params1, _ = model.init(jax.random.PRNGKey(0), ShardRules(mesh))
        step1 = jax.jit(make_train_step(model, adamw.AdamWConfig()))
        _, _, m1 = step1(params1, adamw.init_state(params1), batch)
        assert abs(float(m["loss"]) - float(m1["loss"])) < 1e-3
        """
    )


def test_mini_dryrun_cell():
    """The dry-run machinery end-to-end on 8 devices (8-chip 'production')."""
    _run(
        """
        import repro.launch.dryrun as dr
        from repro.launch import mesh as meshmod
        # shrink the production mesh for the 8-device subprocess
        meshmod.make_production_mesh = lambda multi_pod=False: (
            meshmod.make_mesh((2,2,2), ("pod","data","model")) if multi_pod
            else meshmod.make_mesh((2,4), ("data","model")))
        dr.make_production_mesh = meshmod.make_production_mesh
        import dataclasses
        from repro.configs import get_smoke_config
        import repro.configs as C
        smoke = get_smoke_config("qwen3-1.7b")
        smoke = dataclasses.replace(smoke, name="qwen3-1.7b")
        C._MODULES["qwen3-1.7b"].config = lambda: smoke
        rec = dr.lower_lm_cell("qwen3-1.7b", "train_4k", multi_pod=False, verbose=False)
        assert rec["status"] == "ok", rec
        assert rec["roofline"]["flops_per_chip"] > 0
        rec2 = dr.lower_lm_cell("qwen3-1.7b", "decode_32k", multi_pod=True, verbose=False)
        assert rec2["status"] == "ok", rec2
        """
    )


def test_mesh_helper_builds_auto_axes():
    """Every mesh comes from ``launch.mesh.make_mesh`` with ``Auto`` axes:
    the pencil FFT's rolls and the halo path's reshapes are GSPMD code, and
    ``jax.make_mesh``'s default ``Explicit`` axes refuse them."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert mesh.axis_names == ("data", "model")
