"""Shared benchmark utilities: timing, CSV/telemetry output, record writer."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax

from repro import telemetry


def time_fn(fn, *args, warmup: int = 1, iters: int = 3):
    """Median wall-clock per call (seconds), after jit warmup."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def run_cpu_child(code: str, timeout: int, cwd: str | None = None) -> dict:
    """Run a counting leg in a fresh interpreter pinned to the CPU and
    return the JSON object on its last stdout line.

    The legs count collective structure on 8 placeholder CPU devices, which
    only a fresh process can create.  ``JAX_PLATFORMS=cpu`` keeps the child
    off any accelerator: a chip belongs to one process, and the parent may
    hold it.  Suites start these children before their own first JAX work.
    """
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(f"CPU sub-bench failed:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def emit(name: str, us_per_call: float, derived: str = ""):
    """One benchmark row: CSV line on stdout (the legacy contract, kept),
    plus a structured ``bench`` event for any installed telemetry sink."""
    print(f"{name},{us_per_call:.1f},{derived}")
    telemetry.emit(telemetry.BenchEvent(
        name=name, us_per_call=float(us_per_call), derived=derived))


def _json_default(o):
    """numpy/jax scalars (np.bool_, np.int64, np.float32, 0-d arrays) leak
    into suite dicts easily; coerce anything with ``.item()`` rather than
    losing a long measurement run to a TypeError at write time."""
    item = getattr(o, "item", None)
    if callable(item):
        return item()
    raise TypeError(f"Object of type {o.__class__.__name__} "
                    "is not JSON serializable")


def write_record(rec: dict, out: str) -> None:
    """Merge ``rec``'s top-level keys into the JSON record at ``out``.

    The one writer behind every ``BENCH_*.json``: merge-aware (suites that
    refresh one section at a time — e.g. the C2F table vs the precond
    sweep — keep the other sections), atomic (tmp + replace, so an
    interrupted run never truncates a committed record).
    """
    merged = {}
    if os.path.exists(out):
        try:
            with open(out) as f:
                merged = json.load(f)
        except (json.JSONDecodeError, OSError):
            merged = {}
    merged.update(rec)
    from repro.resilience.atomic import atomic_write_json

    atomic_write_json(out, merged, indent=1, default=_json_default)
