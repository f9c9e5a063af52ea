"""Interpolation kernel microbenchmark (the paper's hot spot, §III-C2).

Measures the oracle's CPU throughput and spot-checks the Pallas kernel in
interpret mode (wall-clock on CPU is meaningless for it; its TPU compile
is pinned by tests/test_tpu_compile.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.tricubic import tricubic_displace_pallas


def main():
    rng = np.random.default_rng(0)
    for n in (32, 64):
        f = jnp.asarray(rng.standard_normal((n, n, n)), jnp.float32)
        d = jnp.asarray(rng.uniform(-3, 3, (3, n, n, n)), jnp.float32)
        interp = jax.jit(lambda f, d: kops.tricubic_displace(f, d, method="ref"))
        t = time_fn(interp, f, d)
        pts = n**3
        emit(f"kernel/tricubic_ref_N{n}", t * 1e6, f"{pts/t/1e6:.1f} Mpts/s (CPU)")

        # batched-channel + plan-reuse columns (ISSUE 3: the full sweep with
        # the mesh exchange counts is `benchmarks.run --suite interp`)
        c = 3
        fc = jnp.asarray(rng.standard_normal((c, n, n, n)), jnp.float32)
        tb = time_fn(jax.jit(ref.tricubic_displace_many), fc, d)
        emit(f"kernel/tricubic_batched_C{c}_N{n}", tb * 1e6,
             f"{c*pts/tb/1e6:.1f} Mpts/s;vs-looped={c*t/tb:.2f}x")
        plan = jax.jit(ref.make_interp_plan)(d)
        tp = time_fn(jax.jit(ref.interp_apply), fc, plan)
        tplan = time_fn(jax.jit(ref.make_interp_plan), d)
        emit(f"kernel/tricubic_planned_C{c}_N{n}", tp * 1e6,
             f"{c*pts/tp/1e6:.1f} Mpts/s;plan-build={tplan*1e6:.0f}us "
             f"(amortized over a Newton iteration)")

    # correctness spot check in interpret mode (ensures the kernel path works
    # in the benchmark environment too)
    f = jnp.asarray(rng.standard_normal((16, 16, 32)), jnp.float32)
    d = jnp.asarray(rng.uniform(-3, 3, (3, 16, 16, 32)), jnp.float32)
    out = tricubic_displace_pallas(f, d, tile=(8, 8, 16), halo=4, interpret=True)
    err = float(jnp.max(jnp.abs(out - ref.tricubic_displace(f, d))))
    emit("kernel/tricubic_pallas_interpret_err", err * 1e6, "max-abs-err-times-1e6")


if __name__ == "__main__":
    main()
