"""Transform-coalescing / pipelined pencil-FFT suite (the ISSUE 5 record).

    PYTHONPATH=src python -m benchmarks.run --suite fft

Writes ``BENCH_fft.json`` at the repo root (structure pinned by
``tests/test_coalesce.py::test_bench_fft_record``):

* ``mesh`` — an 8-device pencil-mesh subprocess measuring the three
  communication levers on the lowered/compiled programs:
  - **counted all-to-alls**: the incompressible GN Hessian matvec with the
    coalesced elliptic assembly (``reg_plus_project``) vs the uncoalesced
    composition main used (``reg_apply`` + ``leray`` as separate round
    trips) — the ISSUE acceptance metric (>= 2x reduction, asserted on
    every run) — plus the ``newton_state`` stage-A pattern (div / reg /
    Lap of the same ``v``): eager per-call vs one ``SpectralBatch`` ride;
  - **packed vs unpacked**: all-to-all *bytes* (from the compiled HLO) and
    wall time of a batched forward with ``PencilFFT(packed=...)``;
  - **chunked vs unchunked**: wall time AND counted all-to-alls of a
    batched fwd+inv roundtrip per ``chunk`` setting (incl. the
    ``"auto"`` heuristic with its ``resolve_chunk`` result), with exact
    parity asserted; the ``chunk_winner`` block picks the cheapest
    setting and seeds the first ``repro.autotune`` tuning-cache entry
    (ISSUE 8 satellite);
  - **Armijo Parseval lever** (``armijo_trial``): counted all-to-alls of
    one line-search trial objective with the spectrum-side
    ``reg_energy`` riding the misfit transport's forward batch vs the
    old composition through ``reg_apply`` — >= 2 fewer all-to-alls per
    trial, asserted on every run (the solver-side pin is
    ``tests/test_coalesce.py::test_armijo_trial_drops_transform_ride_pin``).
* ``single_device`` — the LocalFFT leg: eager vs coalesced stage-A wall
  time (rfft batching amortization).

Env knobs: ``BENCH_FFT_TOY=1`` shrinks the grids and redirects the record
to ``results/BENCH_fft_toy.json`` (the ``scripts/smoke.sh`` tripwire —
still asserting the counted-collective structure); ``BENCH_FFT_OUT``
overrides the path.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import emit, time_fn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_fft.json")
TOY_OUT = os.path.join(ROOT, "results", "BENCH_fft_toy.json")

MESH_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, {root_src!r})
import jax, jax.numpy as jnp, numpy as np
from repro.core import objective as obj, semilag
from repro.core.grid import make_grid
from repro.dist.context import DistContext
from repro.dist.pencil_fft import PencilFFT
from repro.launch.mesh import make_mesh
from repro.telemetry import count_collectives
sys.path.insert(0, {root!r})
from benchmarks.common import time_fn

mesh = make_mesh((2, 4), ("data", "model"))
grid = make_grid({grid_shape!r})
# A/B measurement context: never consult the tuning cache this suite seeds
ctx = DistContext(grid, mesh, halo=2, autotune="off")
ops = ctx.ops
rng = np.random.default_rng(0)
n_t = 2

def compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()

def count_a2a(c):
    return count_collectives(c)["all-to-all"]["count"]

# ---- GN Hessian matvec: coalesced vs the uncoalesced composition (main) ----
rho_R = ctx.shard_scalar(jnp.asarray(rng.standard_normal(grid.shape), jnp.float32))
rho_T = ctx.shard_scalar(jnp.asarray(rng.standard_normal(grid.shape), jnp.float32))
prob = obj.Problem(grid, rho_R, rho_T, 1e-2, n_t, True)
v = jax.device_put(
    0.1 * jnp.asarray(rng.standard_normal((3,) + grid.shape), jnp.float32),
    ctx.vector_sharding())
state = jax.jit(lambda vv: obj.newton_state(vv, prob, ops, ctx.interp))(v)
p = jax.device_put(
    jnp.asarray(rng.standard_normal((3,) + grid.shape), jnp.float32),
    ctx.vector_sharding())

def matvec_coalesced(p):
    return obj.gn_hessian_matvec(p, state, prob, ops, ctx.interp)

def matvec_composed(p):  # the pre-coalescing composition, for the A/B count
    rho1_t = semilag.transport_inc_state(p, state.grad_rho_series, state.plan, ctx.interp)
    lamt = semilag.transport_inc_adjoint(-rho1_t, state.plan, ctx.interp)
    bt = semilag.time_integral_b(lamt, state.grad_rho_series, state.plan.dt)
    return ops.reg_apply(p, prob.beta) + ops.leray(bt)

c_co, c_cm = compiled(matvec_coalesced, p), compiled(matvec_composed, p)
ref = c_cm(p)
err_mv = float(jnp.max(jnp.abs(c_co(p) - ref)) / jnp.maximum(jnp.max(jnp.abs(ref)), 1.0))

# ---- newton_state stage A: eager per-call ops vs one SpectralBatch ride ----
def stage_a_eager(v):
    return ops.div(v), ops.reg_apply(v, 1e-2), ops.laplacian(v)

def stage_a_coalesced(v):
    with ops.batch() as sb:
        d, r, l = sb.div(v), sb.reg_apply(v, 1e-2), sb.laplacian(v)
    return d.get(), r.get(), l.get()

c_ae, c_ac = compiled(stage_a_eager, v), compiled(stage_a_coalesced, v)

# ---- packed vs unpacked forward: bytes + wall ----
B = {batch!r}
stack = jnp.asarray(rng.standard_normal((B,) + grid.shape), jnp.float32)
fft_p = PencilFFT(grid, mesh, packed=True)
fft_u = PencilFFT(grid, mesh, packed=False)
fwd_p = compiled(fft_p.fwd_packed, stack)
fwd_u = compiled(fft_u.fwd, stack)
bytes_p = count_collectives(fwd_p)["all-to-all"]["bytes"]
bytes_u = count_collectives(fwd_u)["all-to-all"]["bytes"]

# ---- chunked vs unchunked roundtrip: parity + wall + counted a2a ----
# Exercises resolve_chunk against the AUTO_CHUNK_TARGET_BYTES heuristic:
# each row records the *resolved* fields-per-chunk for this pencil
# footprint and the counted all-to-alls of the compiled roundtrip; the
# winner (fewest a2a launches, wall as tiebreak) seeds the tuning cache —
# the first autotune entry of a fresh checkout.
from repro.dist.pencil_fft import AUTO_CHUNK_TARGET_BYTES, resolve_chunk
ref_spec = fft_p.fwd(stack)
chunks = []
for chunk in (None, 1, 2, 4, "auto"):
    fft_c = PencilFFT(grid, mesh, chunk=chunk)
    rt = compiled(lambda u: fft_c.inv(fft_c.fwd(u)), stack)
    err = float(jnp.max(jnp.abs(fft_c.fwd(stack) - ref_spec)))
    chunks.append({{
        "chunk": 0 if chunk is None else fft_c.chunk,
        "label": str(chunk),
        "resolved_chunk": resolve_chunk(chunk, grid.shape, 2, 4) if chunk is not None else 0,
        "a2a_count": count_a2a(rt),
        "roundtrip_s": time_fn(rt, stack),
        "fwd_max_err": err,
    }})
winner_row = min(chunks, key=lambda r: (r["a2a_count"], r["roundtrip_s"]))
chunk_winner = None if winner_row["label"] == "None" else (
    "auto" if winner_row["label"] == "auto" else winner_row["chunk"])

# seed the tuning cache with the chunk winner (counted mode, beta-agnostic)
from repro.autotune import TunedConfig, TuningCache, cell_key
cache = TuningCache()
cache.put(
    cell_key(grid.shape, 8, None),
    TunedConfig(chunk=chunk_winner, mode="counted", cost=float(winner_row["a2a_count"])),
)

# ---- Armijo trial: Parseval reg energy vs the pre-Parseval composition ----
# (the ISSUE 8 lever: each line-search trial rides the forward spectrum for
# the regularization energy instead of paying a dedicated fwd+inv pair)
from repro.core.planner import make_plan

def trial_parseval(vv):
    jval, _ = obj.evaluate_objective(vv, prob, ops, ctx.interp)
    return jval

def trial_composed(vv):
    reg = 0.5 * grid.inner(vv, ops.reg_apply(vv, prob.beta))
    plan = make_plan(vv, grid, ops, prob.n_t, prob.incompressible, ctx.interp,
                     adjoint=False)
    rho1 = semilag.transport_state(prob.rho_T, plan, ctx.interp)[-1]
    return 0.5 * grid.inner(rho1 - prob.rho_R, rho1 - prob.rho_R) + reg

c_tp, c_tc = compiled(trial_parseval, v), compiled(trial_composed, v)
err_trial = abs(float(c_tp(v)) - float(c_tc(v))) / max(abs(float(c_tc(v))), 1.0)

rec = {{
    "mesh_shape": [2, 4],
    "grid": list(grid.shape),
    "n_t": n_t,
    "batch": B,
    "armijo_trial": {{
        "a2a_parseval": count_a2a(c_tp),
        "a2a_composed": count_a2a(c_tc),
        "parseval_s": time_fn(c_tp, v),
        "composed_s": time_fn(c_tc, v),
        "rel_err": err_trial,
    }},
    "chunk_winner": {{
        "label": winner_row["label"],
        "a2a_count": winner_row["a2a_count"],
        "auto_chunk_target_bytes": AUTO_CHUNK_TARGET_BYTES,
        "auto_resolved_fields": resolve_chunk("auto", grid.shape, 2, 4),
        "cache_path": cache.path,
    }},
    "all_to_alls": {{
        "gn_matvec_coalesced": count_a2a(c_co),
        "gn_matvec_composed": count_a2a(c_cm),
        "stage_a_coalesced": count_a2a(c_ac),
        "stage_a_eager": count_a2a(c_ae),
    }},
    "gn_matvec_rel_err": err_mv,
    "packed_fwd": {{
        "a2a_bytes_packed": int(bytes_p),
        "a2a_bytes_unpacked": int(bytes_u),
        "packed_s": time_fn(fwd_p, stack),
        "unpacked_s": time_fn(fwd_u, stack),
    }},
    "chunks": chunks,
}}
print(json.dumps(rec))
"""


def _mesh_record(grid_shape, batch) -> dict:
    code = MESH_BODY.format(
        root=ROOT, root_src=os.path.join(ROOT, "src"),
        grid_shape=tuple(grid_shape), batch=int(batch),
    )
    return common.run_cpu_child(code, timeout=1800)


def _single_device(n: int) -> dict:
    from repro.core.grid import make_grid
    from repro.core.spectral import SpectralOps

    grid = make_grid(n)
    ops = SpectralOps(grid)
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal((3,) + grid.shape), jnp.float32)

    def eager(v):
        return ops.div(v), ops.reg_apply(v, 1e-2), ops.laplacian(v)

    def coalesced(v):
        with ops.batch() as sb:
            d, r, l = sb.div(v), sb.reg_apply(v, 1e-2), sb.laplacian(v)
        return d.get(), r.get(), l.get()

    e, c = jax.jit(eager), jax.jit(coalesced)
    de, dc = e(v), c(v)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(de, dc))
    return {
        "n": n,
        "eager_s": time_fn(e, v, iters=5),
        "coalesced_s": time_fn(c, v, iters=5),
        "max_err": err,
    }


def measure(toy: bool = False) -> dict:
    mesh_grid = (8, 8, 16) if toy else (16, 16, 32)
    return {
        "mesh": _mesh_record(mesh_grid, batch=6 if toy else 12),
        "single_device": _single_device(16 if toy else 48),
    }


def write_record(rec: dict, out: str) -> None:
    common.write_record(rec, out)


def main(out: str | None = None):
    toy = bool(int(os.environ.get("BENCH_FFT_TOY", "0")))
    out = out or os.environ.get("BENCH_FFT_OUT") or (TOY_OUT if toy else DEFAULT_OUT)
    rec = measure(toy=toy)
    write_record(rec, out)

    m = rec["mesh"]
    a2a = m["all_to_alls"]
    emit(
        "fft/mesh_gn_matvec",
        0.0,
        f"a2a_coalesced={a2a['gn_matvec_coalesced']};"
        f"a2a_composed={a2a['gn_matvec_composed']};"
        f"reduction={a2a['gn_matvec_composed'] / max(a2a['gn_matvec_coalesced'], 1):.2f}x",
    )
    pf = m["packed_fwd"]
    emit(
        "fft/mesh_packed_fwd",
        pf["packed_s"] * 1e6,
        f"unpacked={pf['unpacked_s']*1e6:.0f}us;"
        f"bytes={pf['a2a_bytes_packed']}/{pf['a2a_bytes_unpacked']}",
    )
    for row in m["chunks"]:
        emit(f"fft/mesh_chunk_{row['label']}", row["roundtrip_s"] * 1e6,
             f"chunk={row['chunk']};a2a={row.get('a2a_count', '?')};"
             f"err={row['fwd_max_err']:.1e}")
    cw = m["chunk_winner"]
    emit("fft/mesh_chunk_winner", 0.0,
         f"label={cw['label']};a2a={cw['a2a_count']};"
         f"auto_fields={cw['auto_resolved_fields']};cache={cw['cache_path']}")
    at = m["armijo_trial"]
    emit("fft/mesh_armijo_trial", at["parseval_s"] * 1e6,
         f"composed={at['composed_s']*1e6:.0f}us;"
         f"a2a={at['a2a_parseval']}/{at['a2a_composed']}")
    sd = rec["single_device"]
    emit(
        f"fft/local_N{sd['n']}",
        sd["coalesced_s"] * 1e6,
        f"eager={sd['eager_s']*1e6:.0f}us;"
        f"speedup={sd['eager_s']/max(sd['coalesced_s'], 1e-12):.2f}x",
    )

    # the tentpole's structural claims, enforced on every run (incl. toy)
    assert 2 * a2a["gn_matvec_coalesced"] <= a2a["gn_matvec_composed"], a2a
    assert 2 * a2a["stage_a_coalesced"] <= a2a["stage_a_eager"], a2a
    assert m["gn_matvec_rel_err"] < 1e-3, m["gn_matvec_rel_err"]
    assert pf["a2a_bytes_packed"] < pf["a2a_bytes_unpacked"], pf
    for row in m["chunks"]:
        assert row["fwd_max_err"] < 1e-3, row
    assert sd["max_err"] < 1e-3, sd
    # ISSUE 8: the Parseval trial saves at least one full transform ride
    assert at["a2a_composed"] - at["a2a_parseval"] >= 2, at
    assert at["rel_err"] < 1e-4, at
    print(f"# wrote {out}")


if __name__ == "__main__":
    main()
