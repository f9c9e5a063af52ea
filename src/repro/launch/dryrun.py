"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage (CPU container; 512 placeholder devices):
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json
    PYTHONPATH=src python -m repro.launch.dryrun --registration --multi-pod

For each cell: ``jit(step).lower(**input_specs).compile()`` on the
production mesh (16x16 single-pod / 2x16x16 multi-pod), then prints
``compiled.memory_analysis()`` (fits-in-HBM proof) and harvests
``cost_analysis()`` + the HLO collective schedule for EXPERIMENTS
§Dry-run / §Roofline.  ShapeDtypeStructs only — nothing is allocated.
"""
# The first two statements MUST precede any jax import: jax locks the
# device count at first init.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as rl
from repro.configs import REGISTRATION_GRIDS, get_config, list_archs
from repro.configs.common import SHAPES, batch_spec, is_cell_supported, token_inputs
from repro.launch.mesh import make_production_mesh
from repro.models.common import ShardRules
from repro.optim import adamw
from repro.train.steps import build_model, make_prefill_step, make_serve_step, make_train_step


def _with_sharding(shapes, specs, mesh):
    """Attach NamedShardings onto a ShapeDtypeStruct tree."""
    flat_s, tdef = jax.tree.flatten(shapes)
    flat_p = jax.tree.flatten(specs, is_leaf=lambda s: isinstance(s, P))[0]
    assert len(flat_s) == len(flat_p), (len(flat_s), len(flat_p))
    out = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, p))
        for a, p in zip(flat_s, flat_p)
    ]
    return tdef.unflatten(out)


def _eval_shape_with_specs(fn, *args):
    """eval_shape a (tree, specs) returning fn; specs captured statically."""
    box = {}

    def wrapper(*a):
        tree, specs = fn(*a)
        box["specs"] = specs
        return tree

    shapes = jax.eval_shape(wrapper, *args)
    return shapes, box["specs"]


# --------------------------------------------------------------------------- #
# sharding/dispatch profiles (EXPERIMENTS §Perf hillclimbs)
# --------------------------------------------------------------------------- #
# "baseline" = the paper-faithful-by-default FSDP+TP rules.
# "optimized" = per-arch fixes found by the hypothesis->measure loop:
#   * dense <=8B archs: drop FSDP (params fit model-sharded); kills GSPMD's
#     contracting-dim activation all-reduces (the 2-9 TB/chip pathologies).
#   * qwen3-moe: 2-D expert weights (E over model, d_ff over data) +
#     token-sharded dispatch groups + explicit group-sharding hints ->
#     dispatch lowers to all-to-all instead of data-axis all-reduce.
#   * gemma3: block-local sliding-window attention is always on (exact);
#     the profile additionally drops FSDP.
PROFILES: dict = {
    "baseline": {},
    "optimized": {
        "gemma-7b": {"rules": {"fsdp": None}, "cfg": {"remat_policy": "dots"}},
        "gemma3-1b": {"rules": {"fsdp": None}},
        "minitron-4b": {"rules": {"fsdp": None}},
        "qwen3-1.7b": {"rules": {"fsdp": None}},
        "mamba2-130m": {"rules": {"fsdp": None}},
        "seamless-m4t-large-v2": {"rules": {"fsdp": None}},
        "zamba2-2.7b": {"rules": {"fsdp": None}},
        "moonshot-v1-16b-a3b": {
            "rules": {"fsdp": None, "moe_embed": None, "moe_ff": "data"},
            "cfg": {"moe_token_shard": 16},
        },
        "qwen3-moe-235b-a22b": {
            "rules": {"fsdp": None, "moe_embed": None, "moe_ff": "data"},
            "cfg": {"moe_token_shard": 16},
        },
    },
}


# --------------------------------------------------------------------------- #
# LM cells
# --------------------------------------------------------------------------- #
def _lower_one(cfg, shape, mesh, kind, rule_overrides=None):
    """Lower+compile one step program for a given depth-variant config."""
    rules = ShardRules(mesh, overrides=rule_overrides)
    model = build_model(cfg)
    pshapes, pspecs = _eval_shape_with_specs(
        lambda k: model.init(k, rules), jax.random.PRNGKey(0)
    )
    params_in = _with_sharding(pshapes, pspecs, mesh)
    inp_shapes, inp_specs = token_inputs(cfg, shape, mesh)
    batch_in = _with_sharding(inp_shapes, inp_specs, mesh)

    if kind == "train":
        opt_shapes = jax.eval_shape(adamw.init_state, pshapes)
        opt_in = _with_sharding(opt_shapes, adamw.state_specs(pspecs), mesh)
        step = make_train_step(model, adamw.AdamWConfig())
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(params_in, opt_in, batch_in)
    elif kind == "prefill":
        step = make_prefill_step(model)
        lowered = jax.jit(step).lower(params_in, batch_in)
    else:  # decode: one new token against a seq_len-long cache
        b, s = shape["batch"], shape["seq"]
        if cfg.enc_layers:
            cshapes, cspecs = _eval_shape_with_specs(
                lambda: model.cache_init(b, s // 2, rules, enc_len=s // 2)
            )
        else:
            cshapes, cspecs = _eval_shape_with_specs(lambda: model.cache_init(b, s, rules))
        caches_in = _with_sharding(cshapes, cspecs, mesh)
        bspec = batch_spec(mesh, b)
        tok_in = jax.ShapeDtypeStruct(
            (b, 1), jnp.int32, sharding=NamedSharding(mesh, P(*bspec, None))
        )
        pos_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
        step = make_serve_step(model)
        lowered = jax.jit(step, donate_argnums=(3,)).lower(params_in, tok_in, pos_in, caches_in)
    return lowered.compile(), rules


def _depth_variant(cfg, n_groups: int):
    """Shallow UNROLLED variant: scan would hide per-layer cost again."""
    import dataclasses

    return dataclasses.replace(cfg, n_layers=len(cfg.layer_pattern) * n_groups,
                               enc_layers=(n_groups if cfg.enc_layers else 0),
                               scan_layers=False)


def lower_lm_cell(
    arch: str, shape_name: str, multi_pod: bool, verbose: bool = True, profile: str = "baseline"
) -> dict:
    import dataclasses as _dc

    from repro.models import hints

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    cfg = get_config(arch)
    prof = PROFILES.get(profile, {}).get(arch, {})
    rule_overrides = prof.get("rules")
    if prof.get("cfg"):
        cfg = _dc.replace(cfg, **prof["cfg"])
    hints.set_mesh(mesh if profile != "baseline" else None)
    shape = SHAPES[shape_name]
    ok, why = is_cell_supported(cfg, shape_name)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape["kind"],
        "profile": profile,
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    kind = shape["kind"]
    n_active = cfg.active_param_count()
    tokens_per_step = shape["batch"] * shape["seq"]
    if kind == "train":
        model_flops = 6.0 * n_active * tokens_per_step
    elif kind == "prefill":
        model_flops = 2.0 * n_active * tokens_per_step
    else:
        model_flops = 2.0 * n_active * shape["batch"]

    t0 = time.time()
    # full-depth compile: THE dry-run artifact (sharding validity + memory)
    compiled, rules = _lower_one(cfg, shape, mesh, kind, rule_overrides)
    t_compile = time.time() - t0

    # XLA cost_analysis counts while-loop bodies ONCE (not x trip count), so
    # scanned layer stacks are undercounted.  All our stacks are homogeneous
    # per pattern group => two-point depth extrapolation is exact:
    #   cost(G) = cost(G=1) + (G-1) * [cost(G=2) - cost(G=1)]
    g_full = cfg.n_groups
    enc_scale = cfg.enc_layers if cfg.enc_layers else None
    g_full = enc_scale or g_full
    # depth probes feed the roofline table, which is single-pod only; the
    # multi-pod pass only has to prove the "pod" axis shards + memory fits.
    if g_full > 1 and not multi_pod:
        c1, _ = _lower_one(_depth_variant(cfg, 1), shape, mesh, kind, rule_overrides)
        c2, _ = _lower_one(_depth_variant(cfg, 2), shape, mesh, kind, rule_overrides)
        r1, coll1 = rl.analyze_compiled(c1, chips=chips)
        r2, coll2 = rl.analyze_compiled(c2, chips=chips)
        flops = r1.flops + (g_full - 1) * max(r2.flops - r1.flops, 0.0)
        nbytes = r1.hbm_bytes + (g_full - 1) * max(r2.hbm_bytes - r1.hbm_bytes, 0.0)
        cbytes = r1.collective_bytes + (g_full - 1) * max(
            r2.collective_bytes - r1.collective_bytes, 0.0
        )
        coll = {
            k: {
                "bytes": int(
                    coll1[k]["bytes"] + (g_full - 1) * max(coll2[k]["bytes"] - coll1[k]["bytes"], 0)
                ),
                "count": coll1[k]["count"]
                + (g_full - 1) * max(coll2[k]["count"] - coll1[k]["count"], 0),
            }
            for k in coll1
            if isinstance(coll1[k], dict)
        }
        roof = rl.Roofline(
            flops=flops, hbm_bytes=nbytes, collective_bytes=cbytes,
            chips=chips, model_flops=model_flops,
            hbm_bytes_model=rl.analytic_memory_bytes(cfg, shape, chips),
        )
    else:
        roof, coll = rl.analyze_compiled(compiled, chips=chips, model_flops=model_flops)
        roof.hbm_bytes_model = rl.analytic_memory_bytes(cfg, shape, chips)
    mem = rl.memory_analysis_dict(compiled)
    rec.update(
        {
            "status": "ok",
            "t_compile_s": round(t_compile, 2),
            "params_total": cfg.param_count(),
            "params_active": n_active,
            "sharding_fallbacks": [f"{l}:{d}" for l, d, _ in rules.fallbacks],
            "memory": mem,
            "collectives": {
                k: v for k, v in coll.items() if isinstance(v, dict) and v["count"]
            },
            "roofline": roof.to_dict(),
        }
    )
    if verbose:
        print(f"--- {arch} x {shape_name} on {rec['mesh']} ---")
        print("memory_analysis:", mem)
        print(
            f"cost: flops/chip={roof.flops:.3e} bytes/chip={roof.hbm_bytes:.3e} "
            f"coll_bytes/chip={roof.collective_bytes:.3e}"
        )
        print(
            f"roofline: compute={roof.t_compute:.4f}s memory={roof.t_memory_model:.4f}s "
            f"(xla-ub {roof.t_memory:.4f}s) collective={roof.t_collective:.4f}s "
            f"-> {roof.bottleneck}"
            f" | useful-flops={roof.useful_flops_ratio:.3f} mfu_bound={roof.mfu_bound:.3f}"
        )
    return rec


# --------------------------------------------------------------------------- #
# registration cells (the paper's own workload)
# --------------------------------------------------------------------------- #
def _reg_component_costs(grid, ctx, rcfg, mesh, chips):
    """Per-component roofline via n_t two-point extrapolation.

    XLA's cost analysis gives FFTs zero flops and counts scan bodies once,
    so: (i) bytes & collective bytes come from compiling the gradient eval
    and one GN Hessian matvec at n_t=1 and n_t=2 and extrapolating to the
    paper's n_t=4; (ii) flops use the paper's analytic model
    (§III-C4: a 3-D FFT is 2.5 * 3 * N^3 log2 N flops, interpolation is
    ~600 flops/point).  Collective bytes split all-to-all (FFT transpose)
    vs collective-permute (interpolation halo) — the paper's own
    FFT-comm / interp-comm table columns.
    """
    import dataclasses as _dc

    from repro.core import objective as obj

    sshape = jax.ShapeDtypeStruct(grid.shape, jnp.float32, sharding=ctx.scalar_sharding())
    vshape = jax.ShapeDtypeStruct((3,) + grid.shape, jnp.float32, sharding=ctx.vector_sharding())

    def costs_at(n_t: int):
        prob_kw = dict(grid=grid, beta=rcfg.beta, incompressible=rcfg.incompressible)

        def grad_eval(v, rho_R, rho_T):
            prob = obj.Problem(rho_R=rho_R, rho_T=rho_T, n_t=n_t, **prob_kw)
            st = obj.newton_state(v, prob, ctx.ops, ctx.interp)
            return st.g

        def matvec(vt, v, rho_R, rho_T):
            prob = obj.Problem(rho_R=rho_R, rho_T=rho_T, n_t=n_t, **prob_kw)
            st = obj.newton_state(v, prob, ctx.ops, ctx.interp)
            return obj.gn_hessian_matvec(vt, st, prob, ctx.ops, ctx.interp)

        cg = jax.jit(grad_eval).lower(vshape, sshape, sshape).compile()
        cm = jax.jit(matvec).lower(vshape, vshape, sshape, sshape).compile()
        rg, collg = rl.analyze_compiled(cg, chips=chips)
        rm, collm = rl.analyze_compiled(cm, chips=chips)
        # matvec-only = (state+matvec) - state
        return rg, collg, rm, collm

    g1, cg1, m1, cm1 = costs_at(1)
    g2, cg2, m2, cm2 = costs_at(2)
    nt = rcfg.n_t

    def extrap(a, b):
        return a + (nt - 1) * max(b - a, 0.0)

    def extrap_coll(c1, c2):
        return {
            k: {
                "bytes": int(c1[k]["bytes"] + (nt - 1) * max(c2[k]["bytes"] - c1[k]["bytes"], 0)),
                "count": c1[k]["count"] + (nt - 1) * max(c2[k]["count"] - c1[k]["count"], 0),
            }
            for k in c1
            if isinstance(c1[k], dict)
        }

    grad_bytes = extrap(g1.hbm_bytes, g2.hbm_bytes)
    grad_coll = extrap_coll(cg1, cg2)
    mv_bytes = extrap(m1.hbm_bytes, m2.hbm_bytes) - grad_bytes  # isolate the matvec
    mv_coll = {
        k: {
            "bytes": max(extrap_coll(cm1, cm2)[k]["bytes"] - grad_coll[k]["bytes"], 0),
            "count": max(extrap_coll(cm1, cm2)[k]["count"] - grad_coll[k]["count"], 0),
        }
        for k in grad_coll
    }

    # paper's analytic flops (per chip): gradient ~ 2 transports + elliptic
    # ops; matvec ~ 8 n_t FFTs + 4 n_t interpolations (§III-C4)
    n3 = grid.num_points
    log_n = max(grid.shape[0].bit_length() - 1, 1)
    fft_flops = 7.5 * n3 * log_n  # one 3-D FFT (paper's constant)
    interp_flops = 600.0 * n3
    mv_flops = (8 * nt * fft_flops + 4 * nt * interp_flops) / chips
    grad_flops = (6 * nt * fft_flops + 2 * nt * interp_flops + 8 * fft_flops) / chips
    return {
        "gradient": {
            "flops_analytic_per_chip": grad_flops,
            "hbm_bytes_per_chip": grad_bytes,
            "collectives": grad_coll,
            "t_compute_s": grad_flops / rl.PEAK_FLOPS,
            "t_memory_s": grad_bytes / rl.HBM_BW,
            "t_collective_s": sum(v["bytes"] for v in grad_coll.values()) / rl.ICI_BW,
        },
        "hessian_matvec": {
            "flops_analytic_per_chip": mv_flops,
            "hbm_bytes_per_chip": mv_bytes,
            "collectives": mv_coll,
            "t_compute_s": mv_flops / rl.PEAK_FLOPS,
            "t_memory_s": mv_bytes / rl.HBM_BW,
            "t_collective_s": sum(v["bytes"] for v in mv_coll.values()) / rl.ICI_BW,
        },
    }


def lower_registration_cell(name: str, multi_pod: bool, verbose: bool = True, rcfg=None) -> dict:
    from repro.core import gauss_newton as gn
    from repro.core import objective as obj
    from repro.core.grid import make_grid
    from repro.dist.context import DistContext

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rcfg = rcfg or REGISTRATION_GRIDS[name]
    grid = make_grid(rcfg.grid)
    axes = (("pod", "data"), "model") if multi_pod else ("data", "model")
    ctx = DistContext(grid, mesh, axes=axes, halo=rcfg.halo)
    cfg = gn.GNConfig(beta=rcfg.beta, n_t=rcfg.n_t, incompressible=rcfg.incompressible)

    def reg_step(it, g0, rho_R, rho_T):
        prob = obj.Problem(
            grid=grid,
            rho_R=rho_R,
            rho_T=rho_T,
            beta=rcfg.beta,
            n_t=rcfg.n_t,
            incompressible=rcfg.incompressible,
        )
        return gn.newton_iteration(it, g0, prob, ctx.ops, cfg, interp=ctx.interp)

    vshape = jax.ShapeDtypeStruct(
        (3,) + grid.shape, jnp.float32, sharding=ctx.vector_sharding()
    )
    sshape = jax.ShapeDtypeStruct(
        grid.shape, jnp.float32, sharding=ctx.scalar_sharding()
    )
    g0 = jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(mesh, P()))

    t0 = time.time()
    lowered = jax.jit(reg_step, donate_argnums=(0,)).lower(
        gn.Iterate(vshape, vshape), g0, sshape, sshape)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = rl.memory_analysis_dict(compiled)
    rec = {
        "arch": name,
        "shape": "x".join(map(str, rcfg.grid)),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": "gn_newton_iteration",
        "status": "ok",
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "memory": mem,
    }
    # component probes (4 extra compiles) only on single-pod, and only for
    # grids <= 256^3-class: bytes/collectives scale linearly per shard, so
    # 512^3/1024^3 rows are extrapolated in EXPERIMENTS from the 256^3 probe.
    if not multi_pod and grid.num_points <= 256**3 * 1.2:
        rec["components"] = _reg_component_costs(grid, ctx, rcfg, mesh, chips)
    if verbose:
        print(f"--- {name} ({rec['shape']}) on {rec['mesh']} ---")
        print("memory_analysis:", mem)
        for comp, c in rec.get("components", {}).items():
            print(
                f"  {comp}: compute={c['t_compute_s']:.5f}s memory={c['t_memory_s']:.5f}s "
                f"collective={c['t_collective_s']:.5f}s"
            )
    return rec


def lower_multilevel_cell(name: str, multi_pod: bool, verbose: bool = True, rcfg=None) -> dict:
    """Lower+compile every level of a coarse-to-fine ladder on the mesh.

    Per level: the GN ``newton_iteration`` program on the level's derived
    ``DistContext`` (coarse matvecs are 8-64x cheaper — the grid-continuation
    lever) plus the spectral prolongation program that carries the warm start
    up the ladder (pencil-FFT truncation/zero-pad; its all-to-all bytes are
    the ladder's only extra communication).
    """
    from repro.core import gauss_newton as gn
    from repro.core import objective as obj
    from repro.core.grid import make_grid
    from repro.dist.context import DistContext
    from repro.multilevel import transfer

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rcfg = rcfg or REGISTRATION_GRIDS[name]
    if not rcfg.levels:
        raise ValueError(f"{name} has no multilevel ladder configured")
    axes = (("pod", "data"), "model") if multi_pod else ("data", "model")
    fine_grid = make_grid(rcfg.grid)
    fine_ctx = DistContext(fine_grid, mesh, axes=axes, halo=rcfg.halo)
    cfg = gn.GNConfig(beta=rcfg.beta, n_t=rcfg.n_t, incompressible=rcfg.incompressible)

    rec = {
        "arch": name,
        "shape": "x".join(map(str, rcfg.grid)),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": "multilevel_ladder",
        "status": "ok",
        "levels": [],
    }
    prev_ctx = None
    for shape in rcfg.levels:
        shape = tuple(shape)
        grid = fine_grid if shape == fine_grid.shape else make_grid(shape)
        ctx = fine_ctx if shape == fine_grid.shape else fine_ctx.coarsen(shape)

        def reg_step(it, g0, rho_R, rho_T, _grid=grid, _ctx=ctx):
            prob = obj.Problem(
                grid=_grid, rho_R=rho_R, rho_T=rho_T, beta=rcfg.beta,
                n_t=rcfg.n_t, incompressible=rcfg.incompressible,
            )
            return gn.newton_iteration(it, g0, prob, _ctx.ops, cfg, interp=_ctx.interp)

        vshape = jax.ShapeDtypeStruct((3,) + grid.shape, jnp.float32, sharding=ctx.vector_sharding())
        sshape = jax.ShapeDtypeStruct(grid.shape, jnp.float32, sharding=ctx.scalar_sharding())
        g0 = jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(mesh, P()))
        t0 = time.time()
        compiled = jax.jit(reg_step, donate_argnums=(0,)).lower(
            gn.Iterate(vshape, vshape), g0, sshape, sshape).compile()
        t_newton = time.time() - t0
        level_rec = {
            "shape": list(shape),
            "t_compile_s": round(t_newton, 2),
            "memory": rl.memory_analysis_dict(compiled),
            "fine_equiv_matvec_weight": grid.num_points / fine_grid.num_points,
        }

        if prev_ctx is not None:  # the warm-start prolongation program
            pv = jax.ShapeDtypeStruct(
                (3,) + prev_ctx.grid.shape, jnp.float32, sharding=prev_ctx.vector_sharding()
            )
            cp = jax.jit(
                lambda v, _a=prev_ctx.ops, _b=ctx.ops: transfer.prolong(v, _a, _b)
            ).lower(pv).compile()
            _, coll = rl.analyze_compiled(cp, chips=chips)
            level_rec["prolong_collectives"] = {
                k: v for k, v in coll.items() if isinstance(v, dict) and v["count"]
            }
        rec["levels"].append(level_rec)
        prev_ctx = ctx
        if verbose:
            print(f"--- {name} level {shape} on {rec['mesh']} ---")
            print("memory_analysis:", level_rec["memory"])
    return rec


# --------------------------------------------------------------------------- #
def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--registration", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--profile", default="baseline", choices=list(PROFILES))
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    records = []

    def flush():
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out + ".tmp", "w") as f:
                json.dump(records, f, indent=1)
            os.replace(args.out + ".tmp", args.out)

    def run(fn, *a):
        try:
            records.append(fn(*a))
        except Exception as e:  # a failing cell is a bug — record it loudly
            traceback.print_exc()
            records.append({"args": [str(x) for x in a], "status": "FAILED", "error": str(e)})
        flush()  # incremental: partial sweeps survive interruption

    for mp in meshes:
        if args.registration:
            regs = [
                "claire-256", "claire-512", "claire-1024", "claire-256-inc",
                "claire-brain", "claire-256-ml", "claire-512-ml",
            ]
            for name in regs:
                if REGISTRATION_GRIDS[name].levels:
                    run(lower_multilevel_cell, name, mp)
                else:
                    run(lower_registration_cell, name, mp)
        if args.all:
            for arch in list_archs():
                for shape in SHAPES:
                    run(lower_lm_cell, arch, shape, mp, True, args.profile)
        elif args.arch:
            shapes = [args.shape] if args.shape else list(SHAPES)
            for shape in shapes:
                run(lower_lm_cell, args.arch, shape, mp, True, args.profile)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    n_fail = sum(1 for r in records if r.get("status") == "FAILED")
    print(f"cells: {len(records)}  ok: {sum(1 for r in records if r.get('status')=='ok')} "
          f"skipped: {sum(1 for r in records if r.get('status')=='skipped')}  FAILED: {n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
