"""Autotune sweep suite (the ISSUE 8 record).

    PYTHONPATH=src python -m benchmarks.run --suite autotune

Writes ``BENCH_autotune.json`` at the repo root (structure pinned by
``tests/test_autotune.py::test_bench_autotune_record``): one entry per
``(grid, mesh)`` cell with every candidate knob set the coordinate-descent
sweep scored (``repro.autotune.search``), the winner, its measured (wall)
or counted (deterministic collective count/byte) cost, the preconditioner
race, and the mesh-layout race.  After the sweeps the suite re-resolves
every cell from the tuning cache and records that the SECOND run is pure
cache resolution — no re-sweep (the acceptance pin).

The winners land in the persistent tuning cache
(``results/autotune_cache.json`` — gitignored; ``REPRO_AUTOTUNE_CACHE``
overrides), where ``DistContext``/``gn.solve`` resolve them by default.

Env knobs: ``BENCH_AUTOTUNE_TOY=1`` shrinks the cells and redirects the
record to ``results/autotune_toy.json`` (the ``scripts/smoke.sh``
tripwire); ``BENCH_AUTOTUNE_OUT`` overrides the path.
"""
from __future__ import annotations

import json
import os

from benchmarks import common
from benchmarks.common import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_autotune.json")
TOY_OUT = os.path.join(ROOT, "results", "autotune_toy.json")

SWEEP_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
{cache_env}
import sys, json
sys.path.insert(0, {root_src!r})
import jax, numpy as np
from repro.autotune import resolve_tuned, TuningCache, cell_key
from repro.autotune.search import sweep_cell, sweep_mesh_layouts
from repro.core.grid import make_grid
from repro.launch.mesh import make_mesh

cells = []
for shape in {shapes!r}:
    grid = make_grid(tuple(shape))
    mesh = make_mesh((2, 4), ("data", "model"))
    rec = sweep_cell(grid, mesh, beta=1e-2, include_precond={precond!r})
    # persist the beta-agnostic alias too, so DistContext (which has no
    # beta at construction time) resolves the same winner
    cache = TuningCache()
    tuned = cache.get(rec["cell"])
    if tuned is not None:
        cache.put(cell_key(grid.shape, 8, None), tuned)
    rec["layouts"] = sweep_mesh_layouts(grid, beta=1e-2)
    cells.append(rec)

# ---- second run: every cell must resolve from the cache, no re-sweep ----
second = []
for shape in {shapes!r}:
    t = resolve_tuned(tuple(shape), 8, beta=1e-2)
    second.append({{
        "cell": cell_key(tuple(shape), 8, 1e-2),
        "resolved_from_cache": t is not None,
        "knobs": t.knobs() if t is not None else None,
        "mode": t.mode if t is not None else None,
    }})

print(json.dumps({{"cells": cells, "second_run": second}}))
"""


def _sweep_record(shapes, cache_path=None, precond=True) -> dict:
    cache_env = (
        f"os.environ['REPRO_AUTOTUNE_CACHE'] = {cache_path!r}" if cache_path else ""
    )
    code = SWEEP_BODY.format(
        root_src=os.path.join(ROOT, "src"),
        shapes=[list(s) for s in shapes],
        cache_env=cache_env,
        precond=bool(precond),
    )
    return common.run_cpu_child(code, timeout=1800, cwd=ROOT)


def measure(toy: bool = False) -> dict:
    shapes = [(8, 8, 16), (8, 16, 8)] if toy else [(16, 16, 32), (16, 32, 16)]
    return _sweep_record(shapes, precond=not toy)


def write_record(rec: dict, out: str) -> None:
    common.write_record(rec, out)


def main(out: str | None = None):
    toy = bool(int(os.environ.get("BENCH_AUTOTUNE_TOY", "0")))
    out = out or os.environ.get("BENCH_AUTOTUNE_OUT") or (TOY_OUT if toy else DEFAULT_OUT)
    rec = measure(toy=toy)
    write_record(rec, out)

    for cell in rec["cells"]:
        emit(
            f"autotune/{cell['cell']}",
            0.0,
            f"mode={cell['mode']};winner={json.dumps(cell['winner'])};"
            f"cost={cell['cost']:.4g};trials={len(cell['trials'])}",
        )
        lay = cell["layouts"]
        emit(
            f"autotune/{cell['cell']}/layouts",
            0.0,
            f"winner={lay['winner']};n={len(lay['layouts'])}",
        )
        for pt in cell.get("precond_trials", []):
            emit(
                f"autotune/{cell['cell']}/precond_{pt['variant']}",
                0.0,
                f"cost={pt['cost']:.4g}",
            )
    hits = [s for s in rec["second_run"] if s["resolved_from_cache"]]
    emit("autotune/second_run", 0.0,
         f"resolved={len(hits)}/{len(rec['second_run'])}")

    # structural pins, enforced on every run (incl. toy)
    assert rec["cells"], rec
    for cell in rec["cells"]:
        assert cell["trials"], cell["cell"]
        # coordinate descent only ever accepts improvements: the winner is
        # never worse than the defaults trial (trials[0])
        assert cell["cost"] <= cell["trials"][0]["cost"] * (1 + 1e-9), cell["cell"]
        assert cell["layouts"]["layouts"], cell["cell"]
    assert all(s["resolved_from_cache"] for s in rec["second_run"]), rec["second_run"]
    print(f"# wrote {out}")


if __name__ == "__main__":
    main()
