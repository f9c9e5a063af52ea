#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips it names.

    python bench/run.py --workload claire128-solve --seed 7 --seconds 10 --trace 0

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  Set-up builds the system and the seed's
inputs, and runs one whole warm-up batch so that every program the window
calls is compiled (or loaded from the persistent cache) before it.  The
window then runs whole batches back to back and starts none after
``--seconds``.  Once it has closed, a registration drawn from the seed is
checked against the float64 reference (``bench/reference.py``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces
the window with the profiler and reports its per-layer metrics, each read
by ``bench/metrics/<metric>.py``.  The last line of stdout is one JSON
object; the numbers compared for ``correct`` are printed beside their
limits as the last lines of stderr and under ``checks``, its last key.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration and mix."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = root
        self.bench = load_json(root / "BENCHMARK.json")
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(by_name)}")
        self.name = name
        self.workload = by_name[name]
        conf = {c["name"]: c for c in self.bench["configs"]}[self.workload["config"]]
        self.cfg = load_json(root / conf["file"])
        self.mix = load_json(root / "bench" / "traffic" / f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])

    def metrics(self, kind: str) -> list[dict]:
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


class CompileCount:
    """Programs compiled, and programs loaded from the persistent cache,
    counted through ``jax.monitoring`` (the backend-compile event wraps
    both)."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.requests += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.requests - self.hits

    def __str__(self):
        return f"{self.compiled} compiled, {self.hits} loaded from the cache"


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, however short its compile."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def say(*parts):
    print(f"{time.perf_counter() - T0:8.1f}s", *parts, file=sys.stderr, flush=True)


class System:
    """The system under test for one configuration: ``run_batch(pairs)``
    registers a batch through the configuration's entry point and returns
    one record per registration (counts, status and the answer)."""

    def __init__(self, cfg: dict, devices, field_dtype=None):
        from repro.core import gauss_newton as gn
        from repro.core.grid import make_grid
        from repro.kernels import ops as kops

        self.cfg = cfg
        self.grid = make_grid(tuple(cfg["grid"]))
        # autotune "off": a machine-local tuning cache must not change the knobs
        self.solver = gn.GNConfig(beta=cfg["beta"], n_t=cfg["n_t"], gtol=cfg["gtol"],
                                  interp_method=cfg.get("interp_method", "auto"),
                                  autotune="off", field_dtype=field_dtype)
        self.ctx = None
        self.place = lambda x: x
        if cfg.get("mesh"):
            from repro.dist.context import DistContext
            from repro.launch.mesh import make_mesh

            mesh = make_mesh(tuple(cfg["mesh"]), ("data", "model"),
                             devices=devices[: int(cfg["chips"])])
            self.ctx = DistContext(self.grid, mesh,
                                   halo=kops.ghost_budget(self.grid.shape, cfg["n_t"]),
                                   autotune="off", field_dtype=field_dtype)
            self.place = self.ctx.shard_scalar
        self.steps: list[int] = []  # live slots at each cohort step
        if cfg["path"] == "cohort":
            self.server = self._server()

    def _server(self):
        from repro.launch.reg_serve import CohortServer

        class Server(CohortServer):
            """Keeps each retired subject's carried ``Lap^2 v`` in ``lap2``,
            which the result does not hold."""

            def _retire(self, s, converged, status):
                self.lap2[self._jobs[s].job_id] = self._lap2[s]
                return super()._retire(s, converged, status)

        ops = interp = None
        if self.ctx is not None:
            ops, interp = self.ctx.ops, self.ctx.interp
        srv = Server(self.grid, self.solver, slots=int(self.cfg["slots"]), ops=ops,
                     interp=interp)
        srv.lap2 = {}
        return srv

    def warm_up(self, traffic) -> list[dict]:
        """One batch of the seed's inputs, so that every program the window
        calls is compiled or loaded; a cohort needs only one subject per
        slot to fill and retire each slot once."""
        pairs = traffic.batch(0)
        if self.cfg["path"] == "cohort":
            pairs = pairs[: int(self.cfg["slots"])]
        return self.run_batch(pairs, tag=0)

    def run_batch(self, pairs, tag) -> list[dict]:
        if self.cfg["path"] == "register":
            return [self._register(r, t) for r, t in pairs]
        return self._cohort(pairs, tag)

    def _register(self, rho_r, rho_t) -> dict:
        import jax

        from repro.core.registration import RegistrationConfig, register

        out = register(rho_r, rho_t, RegistrationConfig(solver=self.solver,
                                                        presmooth=self.cfg["presmooth"]),
                       grid=self.grid, ctx=self.ctx)
        jax.block_until_ready(out["v"])
        return {"status": out["status"], "newton_iters": out["newton_iters"],
                "matvecs": out["hessian_matvecs"], "det_min": out["det_min"],
                "v": out["v"], "lap2": out["lap2_v"], "rho1": out["rho_deformed"]}

    def _cohort(self, pairs, tag) -> list[dict]:
        import jax

        from repro.launch.reg_serve import RegJob

        srv = self.server
        srv.results, srv.lap2 = [], {}
        srv.admit(*[RegJob(job_id=(tag, j), rho_R=r, rho_T=t)
                    for j, (r, t) in enumerate(pairs)])
        while srv.queue or srv.active.any():
            srv._fill_slots()
            self.steps.append(int(srv.active.sum()))
            srv.step()
        jax.block_until_ready(list(srv.lap2.values()))
        return [{"status": r.status, "newton_iters": r.newton_iters,
                 "matvecs": r.hessian_matvecs, "v": r.v, "lap2": srv.lap2[r.job_id],
                 "subject": r.job_id[1]}
                for r in sorted(srv.results, key=lambda r: r.job_id[1])]


def reader(root: Path, name: str):
    """The module ``bench/metrics/<name>.py`` under ``root``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(root: Path, kind: str) -> dict:
    """The published peaks of a device kind; a kind not in the table is an
    error, never a default."""
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise SystemExit(f"bench: no published peaks for {kind!r} in bench/peaks.json")
    return table[kind]


def check(reg: dict, pair, cfg: dict) -> dict:
    """The numbers compared with the float64 reference for one registration:
    the reference's relative gradient at the returned velocity (with the
    solver's carried ``Lap^2 v``), ``det(grad y)``, and where the entry
    returns them, the transported template and the smallest determinant
    against the reference's."""
    import numpy as np

    from bench import reference

    v = np.asarray(reg["v"], np.float64)
    ref = reference.evaluate(v, *[np.asarray(a) for a in pair], cfg["n_t"], cfg["presmooth"])
    g = cfg["beta"] * np.asarray(reg["lap2"], np.float64) + ref["b"]
    out = {"grad_rel": float(np.linalg.norm(g) / np.linalg.norm(ref["b0"])),
           "det_min_ref": float(ref["det_min"])}
    if "rho1" in reg:
        d = np.asarray(reg["rho1"], np.float64) - ref["rho1"]
        out["rho1_err"] = float(np.linalg.norm(d) / np.linalg.norm(ref["rho_r"] - ref["rho_t"]))
        out["det_min_err"] = abs(reg["det_min"] - out["det_min_ref"]) / out["det_min_ref"]
    return out


def worst(values, pick=max) -> float:
    """The worst of ``values``; NaN if any is not finite or there is none."""
    import math

    values = list(values)
    if not values or not all(math.isfinite(v) for v in values):
        return math.nan
    return pick(values)


def compare(checked: list[dict], carry: list[float], cfg: dict) -> dict:
    """Each compared number, the worst over the registrations it was read
    on, with its limit (``limits`` of the configuration): name -> (value,
    limit, "<=" or ">")."""
    lim = cfg["limits"]
    out = {"checked": (float(len(checked)), 0.0, ">"),
           "carry_err": (worst(carry), lim["carry_err"], "<=")}
    if checked:
        out["grad_rel"] = (worst(c["grad_rel"] for c in checked), lim["grad_rel"], "<=")
        out["det_min_ref"] = (worst((c["det_min_ref"] for c in checked), min), 0.0, ">")
        for k in ("rho1_err", "det_min_err"):
            if k in checked[0]:
                out[k] = (worst(c[k] for c in checked), lim[k], "<=")
    return out


def sample_of(seed: int, converged: int, cfg: dict) -> list[int]:
    """Which of the window's converged registrations a run checks, drawn
    from the seed: ``checks_per_run`` of the configuration."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 7])
    k = min(converged, int(cfg.get("checks_per_run", 1)))
    return sorted(int(i) for i in rng.choice(converged, size=k, replace=False))


def passed(value, limit, op="<=") -> bool:
    import math

    if not math.isfinite(value):
        return False
    return value > limit if op == ">" else value <= limit


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             field_dtype=None, log=say) -> dict:
    """Set-up, the measured window and the check of one run; returns the
    result object (``correct``, ``attempted``, ``failed``, ``metrics``,
    ``device``, optionally ``breakdown``, and ``checks``)."""
    import jax
    import numpy as np

    from bench import reference
    from bench.generate import Traffic
    from repro import telemetry

    cfg, mix = cell.cfg, cell.mix
    compiles = CompileCount()
    system = System(cfg, devices, field_dtype=field_dtype)
    traffic = Traffic(cfg, mix, seed, place=system.place)
    log(f"set-up: system and inputs built; programs: {compiles}")
    warm = system.warm_up(traffic)
    log(f"set-up: warm-up batch done ({[r['status'] for r in warm]}); programs: {compiles}")
    del warm
    system.steps.clear()
    batches = [traffic.batch(i) for i in (1, 2)]
    jax.block_until_ready(batches)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # no Python tracer: it times every Python call and slows the host
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    c0, h0 = compiles.compiled, compiles.hits
    setup_s = time.perf_counter() - T0
    t0 = time.perf_counter()
    done, i = [], 1
    while True:
        pairs = batches.pop(0)
        regs = system.run_batch(pairs, tag=i)
        done.append((i, pairs, regs))
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
        batches.append(traffic.batch(i + 1))
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.compiled - c0
    log(f"window: {window_s:.3f} s, {sum(len(r) for _, _, r in done)} registrations; "
        f"inside the window {in_window} programs compiled, {compiles.hits - h0} loaded "
        "from the cache")
    peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in devices[: cell.chips]]
    log(f"peak HBM per device: {peaks_in_use}")

    regs = [(r, p) for _, pairs, rs in done for r, p in zip(rs, pairs)]
    good = [(r, p) for r, p in regs if r["status"] == "converged"]
    run = {"cell": cell, "regs": [r for r, _ in regs], "window_s": window_s,
           "steps": list(system.steps), "slots": int(cfg.get("slots", 1)), "trace": None}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": max(p or 0 for p in peaks_in_use)}
    result = {"correct": None, "attempted": len(regs), "failed": len(regs) - len(good)}
    if trace:
        on_tpu = devices[0].platform == "tpu"
        peaks = peaks_for(cell.root, devices[0].device_kind) if on_tpu else None
        run["trace"] = read_trace(window_s, cell.chips, peaks)
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = window_s
    metrics = {}
    if trace:
        for m in cell.metrics("per_layer"):
            value = reader(cell.root, m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics["s_per_reg"] = {"value": window_s / max(len(good), 1), "unit": "s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = run["trace"]["breakdown"]

    # the check, once the window has closed: every converged registration's
    # carried image against its velocity, and a sample of them drawn from
    # the seed against the float64 reference.  One that did not converge
    # claims nothing and counts as failed.
    carry = [reference.carried_image_error(r["v"], r["lap2"]) for r, _ in good]
    sample = [good[k] for k in sample_of(seed, len(good), cfg)]
    sample = [({k: (np.asarray(v) if hasattr(v, "shape") else v) for k, v in r.items()},
               [np.asarray(a) for a in p]) for r, p in sample]
    del done, regs, good, run, batches, system
    jax.effects_barrier()
    log(f"halo_budget_exceeded events: {telemetry.counters().get('halo_budget_exceeded', 0)}")
    t = time.perf_counter()
    checks = compare([check(r, p, cfg) for r, p in sample], carry, cfg)
    log(f"reference check: {time.perf_counter() - t:.1f} s for {len(sample)} registration(s)")
    result["correct"] = all(passed(*c) for c in checks.values())
    result["checks"] = {k: {"value": v, "limit": lim, "must_be": op, "pass": passed(v, lim, op)}
                        for k, (v, lim, op) in checks.items()}
    return result


def read_trace(window_s: float, chips: int, peaks) -> dict:
    """Device busy time, the per-device ops and the breakdown of the traced
    window."""
    from bench import trace_reduce as tr

    prof = tr.load(str(TRACE_DIR))
    ops = tr.device_ops(prof)
    devs = sorted(ops)[:chips]
    busy = [tr.busy(ops[d]) for d in devs]
    first = ops[devs[0]] if devs else []
    out = {"ops": {d: ops[d] for d in devs}, "busy_s": sum(busy) / max(len(busy), 1),
           "window_s": window_s, "peaks": peaks,
           "breakdown": {"device_ops": tr.top_ops(first),
                         "idle_gaps": tr.idle_gaps(first, tr.host_spans(prof))}}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload)
    enable_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (jax.devices()[0] is {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    peaks_for(ROOT, devices[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    report(result)
    return 0


def report(result: dict) -> None:
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} {c['must_be']} {c['limit']!r} "
              f"{'ok' if c['pass'] else 'FAIL'}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
