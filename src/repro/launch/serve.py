"""Batched serving driver: prefill + decode loop over the zoo.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
        --batch 4 --prompt-len 12 --gen-len 32

Production shapes are exercised via the dry-run (decode_32k / long_500k
cells); this driver runs reduced configs end-to-end on local devices.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models.common import ShardRules
from repro.train.steps import build_model, make_serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = make_mesh((jax.device_count(),), ("data",))
    rules = ShardRules(mesh)
    params, _ = model.init(jax.random.PRNGKey(args.seed), rules)

    rng = np.random.default_rng(args.seed)
    b, pl_, gl = args.batch, args.prompt_len, args.gen_len
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (b, pl_)), jnp.int32)
    caches, _ = model.cache_init(b, pl_ + gl, rules)
    serve = jax.jit(make_serve_step(model))

    nxt = prompt[:, :1]
    for t in range(pl_):  # prefill (token-wise; batched prefill via forward())
        nxt, caches = serve(params, prompt[:, t : t + 1], jnp.int32(t), caches)
    t0 = time.time()
    out = []
    tok = nxt
    for t in range(pl_, pl_ + gl):
        tok, caches = serve(params, tok, jnp.int32(t), caches)
        out.append(np.asarray(tok)[:, 0])
    dt = time.time() - t0
    print(f"arch={cfg.name} decoded {gl} tok/seq x {b} seqs in {dt:.2f}s "
          f"({b * gl / dt:.1f} tok/s)")
    print("seq0 token ids:", [int(x) for x in np.stack(out, 1)[0]])


if __name__ == "__main__":
    main()
