"""Batched serving demo: prefill + streaming decode with KV caches.

    PYTHONPATH=src python examples/serve_lm.py

Runs a reduced gemma3 (sliding-window + global attention) through a
prefill-then-decode loop with ring-buffer local caches — the serving path
the decode_32k / long_500k dry-run cells lower at production shapes.
"""
import sys, time
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models.common import ShardRules
from repro.train.steps import build_model, make_serve_step


def main():
    cfg = get_smoke_config("gemma3-1b")
    model = build_model(cfg)
    mesh = make_mesh((1,), ("data",))
    rules = ShardRules(mesh)
    params, _ = model.init(jax.random.PRNGKey(0), rules)

    b, prompt_len, gen_len = 4, 12, 20
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (b, prompt_len)), jnp.int32)

    caches, _ = model.cache_init(b, prompt_len + gen_len, rules)
    serve = jax.jit(make_serve_step(model))

    # prefill token-by-token (production path would batch this)
    tok = prompt[:, :1]
    for t in range(prompt_len):
        nxt, caches = serve(params, prompt[:, t:t+1], jnp.int32(t), caches)
    print(f"prefilled {b} sequences x {prompt_len} tokens")

    t0 = time.time()
    out = []
    tok = nxt
    for t in range(prompt_len, prompt_len + gen_len):
        tok, caches = serve(params, tok, jnp.int32(t), caches)
        out.append(np.asarray(tok)[:, 0])
    dt = time.time() - t0
    print(f"decoded {gen_len} tokens/seq in {dt:.2f}s "
          f"({b*gen_len/dt:.1f} tok/s on CPU)")
    print("sample continuation (token ids):", [int(x) for x in np.stack(out, 1)[0]])


if __name__ == "__main__":
    main()
