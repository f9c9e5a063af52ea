"""Batched / planned interpolation suite (the ISSUE 3 perf record).

    PYTHONPATH=src python -m benchmarks.run --suite interp

Writes ``BENCH_interp.json`` at the repo root (structure pinned by
``tests/test_interp_plan.py::test_bench_interp_record``):

* ``single_device`` — per (N, C): wall time of C looped per-field calls vs
  ONE batched ``tricubic_displace_many`` call vs the planned
  ``interp_apply`` against a prebuilt ``InterpPlan``, plus the plan build
  cost itself (paid once per Newton iteration, amortized over every
  transport + PCG matvec).  Each row also measures the bf16-packed plan
  apply (``planned_bf16_s`` + its relative error vs the f32 plan) and,
  where available, the batched Pallas kernel (``pallas_batched_s``:
  compiled natively on TPU, interpret mode elsewhere at N <= 32 —
  ``pallas_mode`` records which; pinned by ``tests/test_interp_plan.py::
  test_bench_interp_record_bf16_and_pallas_columns``).
* ``mesh`` — an 8-device pencil-mesh subprocess: wall times AND the
  **counted** ``collective_permute`` ops in the lowered program — the
  batched path issues one ghost-exchange sequence per call regardless of
  C, the looped baseline issues C (the paper's Alg. 1 scatter phase, C x
  fewer collective rounds).

Env knobs: ``BENCH_INTERP_TOY=1`` shrinks the grid sweep to 16^3 and
redirects the record to ``results/BENCH_interp_toy.json`` (the
``scripts/smoke.sh`` regression tripwire — fails fast if any path breaks
or the record schema drifts); ``BENCH_INTERP_OUT`` overrides the path.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import emit, time_fn
from repro.kernels import ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_interp.json")
TOY_OUT = os.path.join(ROOT, "results", "BENCH_interp_toy.json")

MESH_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, {root_src!r})
import jax, jax.numpy as jnp, numpy as np
from repro.core.grid import make_grid
from repro.dist.context import DistContext
from repro.launch.mesh import make_mesh
sys.path.insert(0, {root!r})
from benchmarks.common import time_fn

halo = 4
mesh = make_mesh((2, 4), ("data", "model"))
grid = make_grid({grid_shape!r})
ctx = DistContext(grid, mesh, halo=halo, halo_check="off")
rng = np.random.default_rng(0)
f = jnp.asarray(rng.standard_normal((3,) + grid.shape), jnp.float32)
d = jnp.asarray(rng.uniform(-3.9, 3.9, (3,) + grid.shape), jnp.float32)
fs = jax.device_put(f, ctx.vector_sharding())
ds = jax.device_put(d, ctx.vector_sharding())
plan = jax.jit(ctx.interp.make_plan)(ds)

batched = jax.jit(ctx.interp)
looped = jax.jit(lambda ff, dd: jnp.stack([ctx.interp(ff[i], dd) for i in range(3)]))
planned = jax.jit(ctx.interp.apply_plan)

def count_cp(fn, *args):
    return jax.jit(fn).lower(*args).as_text().count("collective_permute")

rec = {{
    "mesh_shape": [2, 4],
    "grid": list(grid.shape),
    "collective_permutes": {{
        "c1": count_cp(ctx.interp, fs[0], ds),
        "batched_c3": count_cp(ctx.interp, fs, ds),
        "planned_c3": count_cp(ctx.interp.apply_plan, fs, plan),
        "looped_c3": count_cp(
            lambda ff, dd: jnp.stack([ctx.interp(ff[i], dd) for i in range(3)]), fs, ds
        ),
    }},
    "looped_s": time_fn(looped, fs, ds),
    "batched_s": time_fn(batched, fs, ds),
    "planned_s": time_fn(planned, fs, plan),
}}
print(json.dumps(rec))
"""


def _single_device(sizes, channels=(3, 4)) -> list[dict]:
    from repro.kernels import tricubic

    rng = np.random.default_rng(0)
    rows = []
    # 5-sample medians at the sizes the record test pins: the batched-vs-
    # looped gap is real but O(10-30%), so keep regeneration noise below it
    iters = {"iters": 5}
    # the Pallas kernel compiles natively on TPU; elsewhere it runs in
    # interpret mode — correct but slow, so measure it at small N only
    on_tpu = jax.default_backend() == "tpu"
    for n in sizes:
        d = jnp.asarray(rng.uniform(-3, 3, (3, n, n, n)), jnp.float32)
        single = jax.jit(lambda ff, dd: ref.tricubic_displace(ff, dd))
        plan_build = jax.jit(ref.make_interp_plan)
        plan_build_bf16 = jax.jit(
            lambda dd: ref.make_interp_plan(dd, dtype=jnp.bfloat16)
        )
        plan = plan_build(d)
        plan_bf16 = plan_build_bf16(d)
        f1 = jnp.asarray(rng.standard_normal((n, n, n)), jnp.float32)
        single_s = time_fn(single, f1, d)
        plan_build_s = time_fn(plan_build, d)
        for c in channels:
            f = jnp.asarray(rng.standard_normal((c, n, n, n)), jnp.float32)
            looped = jax.jit(
                lambda ff, dd, _c=c: jnp.stack(
                    [ref.tricubic_displace(ff[i], dd) for i in range(_c)]
                )
            )
            batched = jax.jit(ref.tricubic_displace_many)
            planned = jax.jit(ref.interp_apply)
            ref_out = planned(f, plan)
            bf16_out = planned(f, plan_bf16)
            bf16_rel_err = float(
                jnp.max(jnp.abs(bf16_out - ref_out)) / jnp.max(jnp.abs(ref_out))
            )
            row = {
                "n": n,
                "c": c,
                "single_s": single_s,
                "looped_s": time_fn(looped, f, d, **iters),
                "batched_s": time_fn(batched, f, d, **iters),
                "planned_s": time_fn(planned, f, plan, **iters),
                "planned_bf16_s": time_fn(planned, f, plan_bf16, **iters),
                "planned_bf16_rel_err": bf16_rel_err,
                "plan_build_s": plan_build_s,
            }
            if on_tpu or n <= 32:
                tile = (8, 8, min(32, n))
                pallas = jax.jit(
                    lambda ff, dd: tricubic.tricubic_displace_pallas_many(
                        ff, dd, tile=tile, interpret=not on_tpu
                    )
                )
                pallas_out = pallas(f, d)
                row["pallas_batched_s"] = time_fn(
                    pallas, f, d, iters=5 if on_tpu else 3
                )
                row["pallas_mode"] = "tpu" if on_tpu else "interpret"
                row["pallas_rel_err"] = float(
                    jnp.max(jnp.abs(pallas_out - batched(f, d)))
                    / jnp.max(jnp.abs(ref_out))
                )
            rows.append(row)
    return rows


def _mesh_record(grid_shape=(16, 16, 32)) -> dict:
    code = MESH_BODY.format(
        root=ROOT, root_src=os.path.join(ROOT, "src"), grid_shape=tuple(grid_shape)
    )
    return common.run_cpu_child(code, timeout=900)


def measure(toy: bool = False) -> dict:
    sizes = (16,) if toy else (32, 64)
    mesh = _mesh_record()  # the CPU child first, before this process's own JAX work
    return {"single_device": _single_device(sizes), "mesh": mesh}


def write_record(rec: dict, out: str) -> None:
    common.write_record(rec, out)


def main(out: str | None = None):
    toy = bool(int(os.environ.get("BENCH_INTERP_TOY", "0")))
    out = out or os.environ.get("BENCH_INTERP_OUT") or (TOY_OUT if toy else DEFAULT_OUT)
    rec = measure(toy=toy)
    write_record(rec, out)
    for r in rec["single_device"]:
        extra = ""
        if "pallas_batched_s" in r:
            extra = (
                f";pallas={r['pallas_batched_s']*1e6:.0f}us"
                f"({r['pallas_mode']})"
            )
        emit(
            f"interp/N{r['n']}_C{r['c']}",
            r["batched_s"] * 1e6,
            f"looped={r['looped_s']*1e6:.0f}us;planned={r['planned_s']*1e6:.0f}us;"
            f"planned_bf16={r['planned_bf16_s']*1e6:.0f}us;"
            f"speedup={r['looped_s']/r['batched_s']:.2f}x;"
            f"planned_speedup={r['looped_s']/r['planned_s']:.2f}x" + extra,
        )
    m = rec["mesh"]
    cp = m["collective_permutes"]
    emit(
        "interp/mesh_2x4",
        m["batched_s"] * 1e6,
        f"looped={m['looped_s']*1e6:.0f}us;cp_c1={cp['c1']};"
        f"cp_batched_c3={cp['batched_c3']};cp_looped_c3={cp['looped_c3']}",
    )
    # the satellite's structural claims, enforced on every run (incl. toy)
    assert cp["batched_c3"] == cp["c1"], cp
    assert cp["planned_c3"] == cp["c1"], cp
    assert cp["looped_c3"] == 3 * cp["c1"], cp
    print(f"# wrote {out}")


if __name__ == "__main__":
    main()
