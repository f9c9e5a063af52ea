"""repro.telemetry (ISSUE 7): schema stability, disabled-mode invariance,
console parity, and the trace_report golden path.

Acceptance pins:
* the JSONL schema round-trips (every event kind -> sink -> load ->
  ``validate_record`` clean) and ``validate_record`` rejects malformed
  records — the CI contract of ``scripts/ci.sh``;
* telemetry enabled vs disabled is invisible to the compiler: the cohort
  solver still compiles ONE executable with a sink installed, and (slow/
  dist) a 2x4-mesh Newton program's counted collectives are bit-identical
  with and without telemetry;
* the console sink / echo path renders byte-identical legacy progress
  lines (default output unchanged);
* ``trace_report`` renders per-phase wall/matvec/collective tables from a
  toy run and its matvec sums match the solver's own meters.
"""
import glob
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from conftest import run_multidevice as _run  # noqa: E402

from repro import telemetry  # noqa: E402
from repro.analysis import trace_report  # noqa: E402
from repro.core import gauss_newton as gn  # noqa: E402
from repro.data.synthetic import synthetic_problem  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_registry():
    """No sink leakage between tests (the registry is process-global)."""
    yield
    for s in telemetry.sinks():
        telemetry.remove_sink(s)


def _one_of_each():
    return [
        telemetry.SpanEvent(name="pcg", wall_s=0.25, path="gn/pcg", depth=1,
                            attrs={"iter": 3}),
        telemetry.NewtonIterEvent(
            source="gn.solve", beta=1e-2, iter=0, j_val=1.0, misfit=0.9,
            reg=0.1, gnorm=2.0, rel_gnorm=1.0, cg_iters=4, step_len=1.0,
            armijo_trials=1, wall_s=0.5),
        telemetry.LevelEvent(level=0, shape=[8, 8, 8], betas=[1e-2],
                             warm_start=False, newton_iters=3,
                             hessian_matvecs=7, fine_equiv_matvecs=0.9,
                             precond_fine_equiv_matvecs=0.0, wall_s=1.0),
        telemetry.LevelStartEvent(level=0, n_levels=2, shape=[8, 8, 8],
                                  betas=[1e-2], warm_start=False),
        telemetry.JobEvent(job_id="job0", newton_iters=4, hessian_matvecs=8,
                           fine_equiv_matvecs=8.0, rel_gnorm=1e-3,
                           converged=True, slot=1, queue_wait_steps=2,
                           admitted_step=3, retired_step=7),
        telemetry.ServeStepEvent(iteration=1, slots=2, occupancy=2,
                                 queue_len=3, refills=0),
        telemetry.CounterEvent(name="halo_budget_exceeded", value=1.0,
                               total=1.0, attrs={"required": 5.0, "budget": 3}),
        telemetry.CollectivesEvent(label="step", collectives={
            "all-to-all": {"count": 4, "bytes": 1024}, "total_bytes": 1024}),
        telemetry.BenchEvent(name="fft/mesh", us_per_call=12.5, derived="x=1"),
        telemetry.SolveEvent(source="gn.solve", newton_iters=3,
                             hessian_matvecs=7),
    ]


# --------------------------------------------------------------------------- #
# schema
# --------------------------------------------------------------------------- #
def test_schema_roundtrip_all_kinds(tmp_path):
    """Every event kind survives sink -> JSONL -> load -> validate."""
    path = tmp_path / "trace.jsonl"
    events = _one_of_each()
    with telemetry.jsonl_sink(path):
        for e in events:
            telemetry.emit(e)
    recs = trace_report.load(str(path))
    assert len(recs) == len(events)
    for rec, ev in zip(recs, events):
        assert rec["v"] == telemetry.SCHEMA_VERSION
        assert rec["kind"] == ev.kind
        assert telemetry.validate_record(rec) == []
    # payload fields survive numerically
    ni = next(r for r in recs if r["kind"] == "newton_iter")
    assert ni["cg_iters"] == 4 and ni["beta"] == 1e-2
    job = next(r for r in recs if r["kind"] == "job")
    assert job["queue_wait_steps"] == 2 and job["converged"] is True


def test_validate_record_rejects_malformed():
    good = telemetry.NewtonIterEvent(
        source="gn.solve", beta=1e-2, iter=0, j_val=1.0, misfit=0.9, reg=0.1,
        gnorm=2.0, rel_gnorm=1.0, cg_iters=4, step_len=1.0).to_record()
    assert telemetry.validate_record(good) == []
    assert telemetry.validate_record("nope")
    assert telemetry.validate_record({**good, "v": 999})
    assert telemetry.validate_record({**good, "kind": "martian"})
    bad = dict(good)
    del bad["cg_iters"]
    assert any("cg_iters" in e for e in telemetry.validate_record(bad))
    no_ts = dict(good)
    no_ts["ts"] = "yesterday"
    assert any("ts" in e for e in telemetry.validate_record(no_ts))


def test_clean_converts_numpy_and_jax():
    rec = telemetry.SolveEvent(
        source="t", newton_iters=np.int64(3),
        hessian_matvecs=jnp.asarray([1, 2])).to_record()
    assert json.loads(json.dumps(rec))["newton_iters"] == 3
    assert rec["hessian_matvecs"] == [1, 2]


# --------------------------------------------------------------------------- #
# runtime: spans, counters, echo
# --------------------------------------------------------------------------- #
def test_span_nesting_and_disabled_mode():
    with telemetry.span("outer") as sp:
        pass
    assert sp.wall_s is None  # disabled: no clock read, no event
    sink = telemetry.ListSink()
    with sink:
        with telemetry.span("outer") as so:
            with telemetry.span("inner") as si:
                si.sync(jnp.ones(3) * 2)
    paths = [r["path"] for r in sink.records]
    assert paths == ["outer/inner", "outer"]
    assert sink.records[0]["depth"] == 1
    assert so.wall_s >= si.wall_s >= 0.0


def test_counter_accumulates_and_emits():
    telemetry.reset_counters()
    sink = telemetry.ListSink()
    with sink:
        telemetry.counter("widgets", 2.0)
        total = telemetry.counter("widgets", 3.0, flavor="blue")
    assert total == 5.0
    assert telemetry.counters()["widgets"] == 5.0
    assert [r["total"] for r in sink.records] == [2.0, 5.0]
    assert sink.records[1]["attrs"] == {"flavor": "blue"}
    telemetry.reset_counters()


def test_echo_renders_legacy_line_without_double_print(capsys):
    ev = telemetry.NewtonIterEvent(
        source="gn.solve", beta=1e-2, iter=3, j_val=1.2345e-1, misfit=1e-1,
        reg=2e-2, gnorm=0.5, rel_gnorm=2.5e-3, cg_iters=7, step_len=0.5)
    legacy = ("[beta=1e-02] it= 3 J=1.2345e-01 misfit=1.0000e-01 "
              "|g|/|g0|=2.500e-03 cg=7 step=0.500")
    telemetry.emit(ev, echo=True)
    assert capsys.readouterr().out.strip() == legacy
    telemetry.emit(ev, echo=False)  # no sink + no echo: silent no-op
    assert capsys.readouterr().out == ""
    with telemetry.ListSink():
        telemetry.add_sink(telemetry.ConsoleSink(verbosity=1))
        telemetry.emit(ev, echo=True)  # ConsoleSink owns rendering: no double
    assert capsys.readouterr().out.strip() == legacy


def test_solver_verbose_output_unchanged(capsys):
    """gn.solve verbose=True prints exactly the legacy per-iteration lines."""
    rho_R, rho_T, _, grid = synthetic_problem(8, n_t=2)
    cfg = gn.GNConfig(beta=1e-2, n_t=2, max_newton=2, max_cg=4, gtol=1e-2)
    out = gn.solve(rho_R, rho_T, grid, cfg, verbose=True)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == len(out["history"])
    for line, rec in zip(lines, out["history"]):
        assert line == (
            f"[beta={rec['beta']:.0e}] it={rec['iter']:2d} "
            f"J={rec['J']:.4e} misfit={rec['misfit']:.4e} "
            f"|g|/|g0|={rec['rel_gnorm']:.3e} cg={rec['cg_iters']} "
            f"step={rec['step']:.3f}"
        )


# --------------------------------------------------------------------------- #
# disabled-mode invariance: telemetry cannot change what gets compiled
# --------------------------------------------------------------------------- #
def test_cohort_one_executable_with_and_without_sink(tmp_path):
    probs = [synthetic_problem(8, n_t=2, amplitude=a) for a in (0.4, 1.0)]
    grid = probs[0][3]
    rho_R = jnp.stack([p[0] for p in probs])
    rho_T = jnp.stack([p[1] for p in probs])
    cfg = gn.GNConfig(beta=1e-2, n_t=2, max_newton=3, max_cg=5, gtol=1e-2)
    off = gn.solve_cohort(rho_R, rho_T, grid, cfg)
    with telemetry.jsonl_sink(tmp_path / "t.jsonl"):
        on = gn.solve_cohort(rho_R, rho_T, grid, cfg)
    # the one-executable pin holds identically in both modes, and the
    # telemetry run converges to the same trajectory
    assert off["compiled_executables"] == on["compiled_executables"] == 1
    assert list(on["newton_iters"]) == list(off["newton_iters"])
    assert list(on["hessian_matvecs"]) == list(off["hessian_matvecs"])


def test_count_collectives_on_hlo_text():
    hlo = "\n".join([
        "ENTRY %main {",
        '  %a2a = f32[4,8]{1,0} all-to-all(%p0), dimensions={0}',
        '  %cp-start = f32[4,8]{1,0} collective-permute-start(%p1)',
        '  %cp-done = f32[4,8]{1,0} collective-permute-done(%cp-start)',
        "}",
    ])
    coll = telemetry.count_collectives(hlo)
    assert coll["all-to-all"]["count"] == 1
    # -start counted once, -done skipped: no double billing
    assert coll["collective-permute"]["count"] == 1
    assert coll["total_count"] == 2
    with pytest.raises(TypeError):
        telemetry.count_collectives(42)


@pytest.mark.slow
@pytest.mark.dist
def test_telemetry_does_not_change_mesh_collectives():
    """On the 2x4 mesh, the compiled cohort Newton program has bit-identical
    per-kind collective counts with a sink installed and without."""
    _run(
        """
        from functools import partial
        from repro import telemetry
        from repro.core import objective as obj, gauss_newton as gn
        from repro.dist.context import DistContext
        from repro.launch.mesh import make_mesh
        from repro.data.synthetic import synthetic_problem

        probs = [synthetic_problem(16, n_t=2, amplitude=a) for a in (0.4, 1.0)]
        grid = probs[0][3]
        rho_R = jnp.stack([p[0] for p in probs])
        rho_T = jnp.stack([p[1] for p in probs])
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = DistContext(grid, mesh, halo=4)
        cfg = gn.GNConfig(n_t=2, max_cg=10)
        prob = obj.Problem(grid, rho_R, rho_T, 1e-2, 2, False)
        vc = gn.with_lap2(jnp.zeros((2, 3) + grid.shape, jnp.float32), ctx.ops, cold=True)
        gf = jnp.full((2,), 1e-30, jnp.float32)
        act = jnp.ones((2,), bool)

        def compile_counts():
            step = jax.jit(partial(gn.newton_iteration_cohort, prob=prob,
                                   ops=ctx.ops, cfg=cfg, interp=ctx.interp))
            return telemetry.count_collectives(step.lower(vc, gf, act))

        off = compile_counts()
        with telemetry.ListSink():
            with telemetry.span("outer"):
                on = compile_counts()
        assert on == off, (on, off)
        assert off["all-to-all"]["count"] > 0  # the mesh program is real
        print("collective parity OK:", off["total_count"])
        """,
        devices=8,
    )


# --------------------------------------------------------------------------- #
# profiler names: device scopes in the compiled step, host spans in a trace
# --------------------------------------------------------------------------- #
# the scopes the benchmark's per-layer readers (bench/metrics/) key on
SCOPES = ("gn.gradient", "gn.pcg", "gn.line_search", "gn.update", "interp.plan",
          "interp.apply", "interp.displace", "fft.fwd", "fft.inv")
SERVE_SPANS = {"serve.step", "serve.fill", "serve.dispatch", "serve.read_log",
               "serve.retire"}


def _step_hlo(kind):
    """Compiled HLO text of a 16^3 Newton step: the cohort step of the
    serving path, or the single solve's iteration."""
    from functools import partial

    from repro.core import objective as obj
    from repro.core.grid import make_grid
    from repro.core.spectral import SpectralOps

    grid = make_grid((16,) * 3)
    cfg = gn.GNConfig(beta=1e-2, n_t=2, max_cg=3, autotune="off")
    img = jnp.zeros(grid.shape, grid.dtype)
    if kind == "cohort":
        v = jnp.zeros((2, 3) + grid.shape, grid.dtype)
        step = gn.make_cohort_step(grid, cfg)
        args = (gn.Iterate(v, v), jnp.full((2,), 1e-30, jnp.float32), jnp.ones((2,), bool),
                jnp.float32(cfg.beta), jnp.stack([img, img]), jnp.stack([img, img]))
    else:
        v = jnp.zeros((3,) + grid.shape, grid.dtype)
        prob = obj.Problem(grid, img, img, cfg.beta, cfg.n_t, False)
        step = jax.jit(partial(gn.newton_iteration, prob=prob, ops=SpectralOps(grid),
                               cfg=cfg))
        args = (gn.Iterate(v, v), jnp.float32(1e-30))
    return step.lower(*args).compile().as_text()


@pytest.mark.parametrize("kind", ["cohort", "single"])
def test_newton_step_names_every_scope(kind):
    """Each scope sits in the op_name stack of the compiled step, and rides
    its ops as the frontend attribute ``<family>="<name>"`` that a TPU trace
    prints in the op's name."""
    hlo = _step_hlo(kind)
    stacks = [s.split("/") for s in re.findall(r'op_name="([^"]*)"', hlo)]
    for name in SCOPES:
        assert any(name in s for s in stacks), name
        assert f'{name.split(".")[0]}="{name}"' in hlo, name
    # the four phases split the step: no op sits in two of them
    phases = [p for p in SCOPES if p.startswith("gn.")]
    assert not any(sum(p in s for p in phases) > 1 for s in stacks)


def test_multilevel_transfer_names_its_transforms():
    """The grid transfers run their transforms under the same ``fft.*``
    scopes as ``SpectralOps``' own."""
    from repro.core.grid import make_grid
    from repro.core.spectral import SpectralOps
    from repro.multilevel import transfer

    fine, coarse = SpectralOps(make_grid((16,) * 3)), SpectralOps(make_grid((8,) * 3))
    f = jnp.zeros((16,) * 3, jnp.float32)
    g = jnp.zeros((8,) * 3, jnp.float32)
    for fn, args in ((transfer.restrict, (f, fine, coarse)),
                     (transfer.prolong, (g, coarse, fine)),
                     (transfer.smooth_restrict, (f, fine, coarse))):
        hlo = jax.jit(fn, static_argnums=(1, 2)).lower(*args).compile().as_text()
        for name in ("fft.fwd", "fft.inv"):
            assert f'fft="{name}"' in hlo, (fn.__name__, name)


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True)
    prof = ProfileData.from_file(max(path, key=os.path.getmtime))
    return {ev.name for plane in prof.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}


def test_serve_step_spans_reach_a_profiler_trace(tmp_path):
    """With no sink installed, one ``CohortServer.step`` leaves its serve
    phases on the host plane of a profiler trace."""
    from repro.launch.reg_serve import CohortServer, RegJob

    probs = [synthetic_problem(8, n_t=2, amplitude=a) for a in (0.4, 0.8)]
    cfg = gn.GNConfig(beta=1e-2, n_t=2, max_newton=4, max_cg=5, gtol=1e-2)
    server = CohortServer(probs[0][3], cfg, slots=1)
    server.admit(*(RegJob(job_id=i, rho_R=p[0], rho_T=p[1]) for i, p in enumerate(probs)))
    server.step()  # compiled outside the trace
    assert not telemetry.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.step()
    finally:
        jax.profiler.stop_trace()
    assert SERVE_SPANS <= _host_event_names(tmp_path)


def test_span_without_sink_neither_syncs_nor_emits(monkeypatch):
    synced = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: synced.append(x) or x)
    with telemetry.span("serve.step") as sp:
        sp.sync(jnp.ones(3))
    assert sp.wall_s is None and synced == []
    sink = telemetry.ListSink()
    with sink:
        with telemetry.span("serve.step") as sp:
            sp.sync(jnp.ones(3))
    assert len(synced) == 1 and sp.wall_s is not None
    assert [r["kind"] for r in sink.records] == ["span"]


def test_cohort_one_executable_under_a_profiler_trace(tmp_path):
    probs = [synthetic_problem(8, n_t=2, amplitude=a) for a in (0.4, 1.0)]
    grid = probs[0][3]
    rho_R = jnp.stack([p[0] for p in probs])
    rho_T = jnp.stack([p[1] for p in probs])
    cfg = gn.GNConfig(beta=1e-2, n_t=2, max_newton=3, max_cg=5, gtol=1e-2)
    off = gn.solve_cohort(rho_R, rho_T, grid, cfg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = gn.solve_cohort(rho_R, rho_T, grid, cfg)
    finally:
        jax.profiler.stop_trace()
    assert off["compiled_executables"] == on["compiled_executables"] == 1
    assert list(on["hessian_matvecs"]) == list(off["hessian_matvecs"])
    assert "gn.cohort_iter" in _host_event_names(tmp_path)


# --------------------------------------------------------------------------- #
# reg_serve job billing events
# --------------------------------------------------------------------------- #
def test_serve_emits_job_and_step_events():
    from repro.launch.reg_serve import CohortServer, RegJob

    probs = [synthetic_problem(8, n_t=2, amplitude=a)
             for a in (0.3, 0.6, 0.9, 1.2)]
    grid = probs[0][3]
    cfg = gn.GNConfig(beta=1e-2, n_t=2, max_newton=6, max_cg=10, gtol=1e-2)
    sink = telemetry.ListSink()
    with sink:
        server = CohortServer(grid, cfg, slots=2)
        server.admit(*(RegJob(job_id=f"j{i}", rho_R=p[0], rho_T=p[1])
                       for i, p in enumerate(probs)))
        results = server.run()
    jobs = [r for r in sink.records if r["kind"] == "job"]
    steps = [r for r in sink.records if r["kind"] == "serve_step"]
    assert len(jobs) == 4 and len(results) == 4
    by_id = {j["job_id"]: j for j in jobs}
    for res in results:
        j = by_id[str(res.job_id)]
        # the event IS the billing record: matvecs/newton match the result
        assert j["hessian_matvecs"] == res.hessian_matvecs
        assert j["newton_iters"] == res.newton_iters
        assert j["retired_step"] >= j["admitted_step"] >= 0
    # the first two jobs are admitted at step 0; later ones waited
    waits = sorted(j["queue_wait_steps"] for j in jobs)
    assert waits[0] == 0 and waits[-1] > 0
    assert steps[-1]["refills"] >= 2  # 4 jobs through 2 slots: >= 2 refills
    assert all(s["occupancy"] <= s["slots"] for s in steps)


# --------------------------------------------------------------------------- #
# trace_report golden path
# --------------------------------------------------------------------------- #
def test_trace_report_golden(tmp_path, capsys):
    rho_R, rho_T, _, grid = synthetic_problem(8, n_t=2)
    cfg = gn.GNConfig(beta=1e-2, n_t=2, max_newton=3, max_cg=5, gtol=1e-2)
    path = tmp_path / "run.jsonl"
    with telemetry.jsonl_sink(path):
        out = gn.solve(rho_R, rho_T, grid, cfg)
    recs = trace_report.load(str(path))
    summary = trace_report.summarize(recs)
    # per-phase matvec accounting closes against the solver's own meter
    assert sum(p["cg_iters"] for p in summary["phases"]) == out["hessian_matvecs"]
    assert sum(p["iters"] for p in summary["phases"]) == out["newton_iters"]
    spans = summary["spans"]
    assert spans["gn.newton_iter"]["count"] == out["newton_iters"]
    assert spans["gn.newton_iter"]["total_s"] > 0
    text = trace_report.render(summary)
    for needle in ("phases", "cg_matvecs", "spans", "gn.newton_iter"):
        assert needle in text, needle
    # the CLI --validate path exits clean on a healthy trace
    assert trace_report.main([str(path), "--validate"]) == 0
    assert "validate" in capsys.readouterr().out


def test_trace_report_validate_fails_on_bad_record(tmp_path, capsys):
    path = tmp_path / "bad.jsonl"
    rec = telemetry.BenchEvent(name="x", us_per_call=1.0).to_record()
    del rec["us_per_call"]
    path.write_text(json.dumps(rec) + "\n")
    assert trace_report.main([str(path), "--validate"]) == 1
    assert "us_per_call" in capsys.readouterr().err
