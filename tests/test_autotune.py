"""repro.autotune: tuning cache robustness, resolver semantics, sweep
persistence, and the mixed-precision (field_dtype) numerics contract.

Fast cases run on the local backend; the mesh legs (tuned-vs-default solver
parity, bf16 registration on a 2x4 pencil mesh) are slow subprocess tests
like the rest of the dist suite.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.autotune import (
    KNOBS_REV,
    SCHEMA_VERSION,
    TunedConfig,
    TuningCache,
    cell_key,
    consult_gn,
    resolve_tuned,
    tuned_replace,
)
from repro.core import gauss_newton as gn
from repro.core import objective as obj
from repro.core.grid import make_grid
from repro.core.spectral import SpectralOps

from conftest import run_multidevice


@pytest.fixture
def cache_path(tmp_path, monkeypatch):
    p = str(tmp_path / "autotune_cache.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", p)
    telemetry.reset_counters()
    yield p
    telemetry.reset_counters()


def _invalid_count():
    return telemetry.counters().get("autotune.cache_invalid", 0.0)


# --------------------------------------------------------------------------- #
# cache file robustness
# --------------------------------------------------------------------------- #
def test_cache_roundtrip(cache_path):
    c = TuningCache()
    key = cell_key((64, 64, 64), 8, 1e-2)
    assert key == "64x64x64/8dev/beta-0.01"
    c.put(key, TunedConfig(chunk=4, interp_method="pallas", mode="wall", cost=1.25))
    t = c.get(key)
    assert t.chunk == 4 and t.interp_method == "pallas" and t.mode == "wall"
    assert c.validate() == []
    # beta-agnostic fallback
    c.put(cell_key((64, 64, 64), 8, None), TunedConfig(chunk=2, mode="wall"))
    assert resolve_tuned((64, 64, 64), 8, beta=3e-3).chunk == 2


def test_cache_corrupt_file_falls_back(cache_path):
    with open(cache_path, "w") as fh:
        fh.write("{this is not json")
    assert TuningCache().get("anything") is None
    assert _invalid_count() >= 1.0
    assert resolve_tuned((8, 8, 8), 1, 1e-2) is None
    assert TuningCache().validate()  # non-empty problem list


def test_cache_schema_version_mismatch_falls_back(cache_path):
    with open(cache_path, "w") as fh:
        json.dump({"schema": SCHEMA_VERSION + 1, "cells": {"k": {}}}, fh)
    assert TuningCache().get("k") is None
    assert _invalid_count() >= 1.0


def test_cache_stale_knobs_rev_falls_back(cache_path):
    cells = {
        cell_key((8, 8, 8), 1, 1e-2): {
            "knobs": {"chunk": 2},
            "mode": "counted",
            "knobs_rev": KNOBS_REV - 1,
        }
    }
    with open(cache_path, "w") as fh:
        json.dump({"schema": SCHEMA_VERSION, "cells": cells}, fh)
    assert resolve_tuned((8, 8, 8), 1, 1e-2) is None
    assert _invalid_count() >= 1.0


def test_cache_rejects_unknown_and_invalid_knobs(cache_path):
    bad_entries = [
        {"knobs": {"warp_factor": 9}, "mode": "counted", "knobs_rev": KNOBS_REV},
        {"knobs": {"chunk": -3}, "mode": "counted", "knobs_rev": KNOBS_REV},
        {"knobs": {"field_dtype": "float8"}, "mode": "counted", "knobs_rev": KNOBS_REV},
        {"knobs": {"interp_method": "cubic"}, "mode": "counted", "knobs_rev": KNOBS_REV},
        {"knobs": {}, "mode": "vibes", "knobs_rev": KNOBS_REV},
    ]
    for entry in bad_entries:
        with open(cache_path, "w") as fh:
            json.dump({"schema": SCHEMA_VERSION, "cells": {"cell": entry}}, fh)
        telemetry.reset_counters()
        assert TuningCache().get("cell") is None, entry
        assert _invalid_count() >= 1.0, entry
        assert TuningCache().validate(), entry


def test_put_refuses_invalid_entry(cache_path):
    with pytest.raises(ValueError):
        TuningCache().put("cell", TunedConfig(chunk="sideways"))


def test_missing_cache_is_valid_and_a_miss(cache_path):
    assert TuningCache().validate() == []
    assert resolve_tuned((8, 8, 8), 1, 1e-2) is None
    assert telemetry.counters().get("autotune.cache_miss", 0.0) >= 1.0


# --------------------------------------------------------------------------- #
# resolver semantics
# --------------------------------------------------------------------------- #
def test_counted_entries_never_apply_dtype_knobs(cache_path):
    c = TuningCache()
    key = cell_key((8, 8, 8), 1, 1e-2)
    c.put(key, TunedConfig(chunk=2, plan_dtype="bfloat16", field_dtype="bfloat16",
                           mode="counted"))
    t = resolve_tuned((8, 8, 8), 1, 1e-2)
    assert t.chunk == 2
    assert t.plan_dtype is None and t.field_dtype is None
    # wall-measured entries do apply them
    c.put(key, TunedConfig(field_dtype="bfloat16", mode="wall"))
    assert resolve_tuned((8, 8, 8), 1, 1e-2).field_dtype == "bfloat16"


def test_tuned_replace_explicit_value_wins(cache_path):
    tuned = TunedConfig(interp_method="pallas", field_dtype="bfloat16", mode="wall")
    defaults = {"interp_method": "auto", "plan_dtype": None, "field_dtype": None}
    cfg = tuned_replace(gn.GNConfig(), tuned, defaults)
    assert cfg.interp_method == "pallas" and cfg.field_dtype == "bfloat16"
    # user-pinned knobs survive
    cfg = tuned_replace(gn.GNConfig(interp_method="ref", field_dtype="float32"),
                        tuned, defaults)
    assert cfg.interp_method == "ref" and cfg.field_dtype == "float32"


def test_consult_gn_cache_hit_skips_sweep(cache_path, monkeypatch):
    """autotune="sweep" must resolve an existing entry WITHOUT re-sweeping."""
    from types import SimpleNamespace

    grid = make_grid((8, 8, 8))
    TuningCache().put(cell_key((8, 8, 8), 4, None),
                      TunedConfig(field_dtype="bfloat16", mode="wall"))
    import repro.autotune.search as search

    def boom(*a, **k):
        raise AssertionError("sweep must not run on a cache hit")

    monkeypatch.setattr(search, "sweep_cell", boom)
    fake_ops = SimpleNamespace(
        fft=SimpleNamespace(mesh=SimpleNamespace(devices=np.zeros(4)),
                            axes=("data", "model"))
    )
    cfg = consult_gn(gn.GNConfig(autotune="sweep"), grid, fake_ops)
    assert cfg.field_dtype == "bfloat16"


def test_gn_autotune_off_ignores_cache(cache_path):
    grid = make_grid((8, 8, 8))
    TuningCache().put(cell_key((8, 8, 8), 1, None),
                      TunedConfig(field_dtype="bfloat16", mode="wall"))
    cfg = gn._tuned_cfg(gn.GNConfig(autotune="off"), grid, None)
    assert cfg.field_dtype is None
    cfg = gn._tuned_cfg(gn.GNConfig(), grid, None)
    assert cfg.field_dtype == "bfloat16"


# --------------------------------------------------------------------------- #
# mixed precision: storage dtype flows, critical accumulations stay f32
# --------------------------------------------------------------------------- #
def _toy_problem(n=12, dtype=jnp.float32):
    grid = make_grid((n, n, n))
    x = np.linspace(0, 2 * np.pi, n, endpoint=False)
    X, Y, Z = np.meshgrid(x, x, x, indexing="ij")
    rho_R = jnp.asarray(np.exp(np.cos(X) * np.cos(Y)), dtype) / np.e
    rho_T = jnp.asarray(np.exp(np.cos(X - 0.5) * np.cos(Y + 0.3)), dtype) / np.e
    return grid, rho_R, rho_T


def test_field_dtype_flows_to_storage():
    grid, rho_R, rho_T = _toy_problem(8)
    ops = SpectralOps(grid, field_dtype="bfloat16")
    v = jnp.zeros((3,) + grid.shape, jnp.float32)
    assert ops.div(v + 1.0).dtype == jnp.bfloat16
    prob = obj.Problem(grid, rho_R, rho_T, 1e-2, 2, False)
    from repro.kernels import ops as kops

    state = obj.newton_state(v, prob, ops, kops.make_interp(method="ref"))
    assert state.rho_series.dtype == jnp.bfloat16
    assert state.lam_series.dtype == jnp.bfloat16
    # the gradient comes out of the f32 time quadrature — never bf16
    assert state.g.dtype == jnp.float32


def test_pcg_recursion_stays_f32_under_bf16_storage():
    """The critical-accumulation pin: with bf16 field storage the PCG
    residual recursion (what the preconditioner sees every iteration) and
    the returned Newton direction must still be f32."""
    grid, rho_R, rho_T = _toy_problem(8)
    ops = SpectralOps(grid, field_dtype="bfloat16")
    prob = obj.Problem(grid, rho_R, rho_T, 1e-2, 2, False)
    cfg = gn.GNConfig(beta=1e-2, n_t=2, max_cg=3, autotune="off",
                      field_dtype="bfloat16")
    seen = []

    def recording_precond(state, prob):
        def pc(r):
            seen.append(r.dtype)
            return ops.precond_project(r, prob.beta, prob.incompressible)

        return pc

    v = jnp.zeros((3,) + grid.shape, jnp.float32)
    v_new, _ = gn.newton_iteration(
        gn.with_lap2(v, ops, cold=True), jnp.float32(1e-30), prob, ops, cfg,
        precond=recording_precond,
    )
    assert seen, "preconditioner never invoked"
    assert all(d == jnp.float32 for d in seen), seen
    # the bf16 preconditioner output was upcast before seeding p0
    assert ops.precond_project(v + 1.0, 1e-2, False).dtype == jnp.bfloat16
    assert v_new.v.dtype == jnp.float32 and v_new.lap2.dtype == jnp.float32


@pytest.mark.slow
def test_bf16_registration_matches_f32_local():
    """ISSUE 8 acceptance: bf16 field storage registers to a residual within
    tolerance of the f32 run at 32^3 on the local backend."""
    from repro.core.registration import RegistrationConfig, register

    grid, rho_R, rho_T = _toy_problem(32)
    base = gn.GNConfig(beta=1e-2, n_t=2, max_newton=4, max_cg=10, autotune="off")
    out32 = register(rho_R, rho_T, RegistrationConfig(solver=base), grid=grid)
    out16 = register(
        rho_R, rho_T,
        RegistrationConfig(solver=dataclasses.replace(base, field_dtype="bfloat16")),
        grid=grid,
    )
    assert out32["residual_rel"] < 0.75
    # bf16 storage must track the f32 solve, not merely "converge somewhat"
    assert abs(out16["residual_rel"] - out32["residual_rel"]) < 0.05, (
        out16["residual_rel"], out32["residual_rel"])
    assert float(jnp.max(jnp.abs(out16["v"] - out32["v"]))) < 0.15


# --------------------------------------------------------------------------- #
# mesh legs (subprocess, 8 placeholder devices)
# --------------------------------------------------------------------------- #
@pytest.mark.slow
@pytest.mark.dist
def test_tuned_vs_default_solver_parity_on_mesh(tmp_path):
    """A counted tuning-cache entry (chunked a2a tiling) must not change the
    solve: tuned-consulting and autotune="off" runs agree to roundoff."""
    cache = str(tmp_path / "cache.json")
    run_multidevice(
        f"""
        import os
        os.environ["REPRO_AUTOTUNE_CACHE"] = {cache!r}
        from repro.autotune import TuningCache, TunedConfig, cell_key
        from repro.core import gauss_newton as gn
        from repro.core.grid import make_grid
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh

        grid = make_grid((16, 16, 32))
        TuningCache().put(cell_key(grid.shape, 8, None),
                          TunedConfig(chunk=2, mode="counted", cost=1.0))
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(TEST_SEED + 3)
        rho_R = jnp.asarray(np.exp(0.3 * rng.standard_normal(grid.shape)), jnp.float32)
        rho_T = jnp.asarray(np.exp(0.3 * rng.standard_normal(grid.shape)), jnp.float32)
        cfg = gn.GNConfig(beta=1e-2, n_t=2, max_newton=2, max_cg=5, autotune="off")

        outs = {{}}
        for label, at in (("tuned", "cache"), ("off", "off")):
            ctx = DistContext(grid, mesh, halo=4, autotune=at)
            if label == "tuned":
                assert ctx.chunk == 2, ctx.chunk
            else:
                assert ctx.chunk is None, ctx.chunk
            out = gn.solve(ctx.shard_scalar(rho_R), ctx.shard_scalar(rho_T),
                           grid, cfg, ops=ctx.ops, interp=ctx.interp)
            outs[label] = np.asarray(out["v"])
        err = float(np.max(np.abs(outs["tuned"] - outs["off"])))
        assert err < 1e-4, err
        """
    )


@pytest.mark.slow
@pytest.mark.dist
def test_bf16_registration_matches_f32_on_mesh():
    """bf16 field storage through the pencil FFT + halo-exchange transport
    path: mesh registration residual within tolerance of the f32 run."""
    run_multidevice(
        """
        import dataclasses
        from repro.core import gauss_newton as gn
        from repro.core.registration import RegistrationConfig, register
        from repro.core.grid import make_grid
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh

        grid = make_grid((16, 16, 32))
        mesh = make_mesh((2, 4), ("data", "model"))
        x = [np.linspace(0, 2*np.pi, n, endpoint=False) for n in grid.shape]
        X, Y, Z = np.meshgrid(*x, indexing="ij")
        rho_R = jnp.asarray(np.exp(np.cos(X) * np.cos(Y)), jnp.float32) / np.e
        rho_T = jnp.asarray(np.exp(np.cos(X - 0.5) * np.cos(Y + 0.3)), jnp.float32) / np.e
        base = gn.GNConfig(beta=1e-2, n_t=2, max_newton=3, max_cg=8, autotune="off")

        res = {}
        for label, fd in (("f32", None), ("bf16", "bfloat16")):
            ctx = DistContext(grid, mesh, halo=4, autotune="off", field_dtype=fd)
            cfg = RegistrationConfig(solver=base)
            out = register(ctx.shard_scalar(rho_R), ctx.shard_scalar(rho_T),
                           cfg, grid=grid, ctx=ctx)
            res[label] = out["residual_rel"]
        assert res["f32"] < 0.9, res
        assert abs(res["bf16"] - res["f32"]) < 0.05, res
        """
    )


# --------------------------------------------------------------------------- #
# committed benchmark record (written by `benchmarks.run --suite autotune`)
# --------------------------------------------------------------------------- #
def test_bench_autotune_record():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_autotune.json")
    assert os.path.exists(path), (
        "run: PYTHONPATH=src python -m benchmarks.run --suite autotune")
    rec = json.load(open(path))
    assert len(rec["cells"]) >= 2, rec.keys()
    for cell in rec["cells"]:
        assert cell["mode"] in ("counted", "wall"), cell
        assert cell["trials"] and "cost" in cell["trials"][0], cell["cell"]
        # defaults are always trialed first; the winner never loses to them
        assert cell["trials"][0]["knobs"] == {}, cell["trials"][0]
        assert cell["cost"] <= cell["trials"][0]["cost"] * (1 + 1e-9), cell["cell"]
        assert cell["layouts"]["winner"], cell["cell"]
    # the acceptance pin: a second run is pure cache resolution, no re-sweep
    assert rec["second_run"], rec.keys()
    for s in rec["second_run"]:
        assert s["resolved_from_cache"], s
