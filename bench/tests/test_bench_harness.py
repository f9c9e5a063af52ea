"""The benchmark harness end to end on the CPU, past its look for a chip:
new cells from added files alone, the float64 reference against the
program, and the lower-precision control and planted faults caught by the
check that decides ``correct``."""
import json
import shutil
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as harness  # noqa: E402

SEED = 2**31 + 977


BASE = {"register": "claire-128.json", "serve": "claire-128-serve.json",
        "cohort": "claire-128-cohort.json"}


def add_cell(root, name, grid, kind):
    """A test-only configuration, mix and workload, added as files next to
    the others and as entries of ``BENCHMARK.json``."""
    base = json.loads((root / "bench" / "configs" / BASE[kind]).read_text())
    base.update(grid=[grid] * 3, name=name)
    if kind == "cohort":
        base.update(amplitudes=[1.0, 0.5], slots=2)
    mix = f"mix-{name}"
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(base))
    (root / "bench" / "traffic" / f"{mix}.json").write_text(
        json.dumps({"batch": 4 if kind == "cohort" else 1}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test", "file": f"bench/configs/{name}.json",
                             "reduced": ["grid"], "why": "test"})
    bench["workloads"].append({"name": f"{name}-cell", "config": name, "traffic": mix,
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.Cell(f"{name}-cell", root=root)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark with the test cells added, and a
    persistent compilation cache of its own (``register`` re-jits its
    Newton step on every call)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield root
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def quiet(*_):
    pass


def run(cell, **kw):
    return harness.run_cell(cell, SEED, 0.0, kw.pop("trace", False), jax.devices(), log=quiet,
                            **kw)


@pytest.mark.parametrize("kind", ["serve", "register"])
def test_added_files_make_a_cell_that_agrees_with_the_reference(root, kind):
    cell = add_cell(root, f"tiny-16-{kind}", 16, kind)
    (root / "bench" / "metrics" / "regs_done.py").write_text(
        "def read(run):\n    return float(len(run['regs']))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "regs_done", "unit": "regs", "better": "higher",
                               "source": "program_counter", "layer": "Newton loop",
                               "moves": "s_per_reg", "workloads": [cell.name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell(cell.name, root=root)
    res = run(cell, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["regs_done"]["value"] == res["attempted"] == 1
    assert res["metrics"]["newton_iters"]["value"] >= 2
    assert "s_per_reg" not in res["metrics"]
    assert res["device"]["busy_s"] > 0
    # the reference and the program agree far inside every limit at 16^3
    c = res["checks"]
    assert c["checked"]["value"] == 1 and c["carry_err"]["value"] < 1e-6
    assert c["grad_rel"]["value"] < 0.2 * c["grad_rel"]["limit"]
    if kind == "register":
        assert c["rho1_err"]["value"] < 1e-5 and c["det_min_err"]["value"] < 1e-5
    assert list(res)[-1] == "checks"


def test_lower_precision_control_is_caught(root):
    res = run(add_cell(root, "tiny-16-control", 16, "serve"), field_dtype="bfloat16")
    assert not res["correct"]
    failing = {k for k, c in res["checks"].items() if not c["pass"]}
    assert failing & {"checked", "grad_rel", "carry_err"}, res["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered", "half_batch"])
def test_planted_fault_is_caught(root, monkeypatch, fault):
    """Each fault the cells can have, planted in the cohort step the window
    drives: its state returned unchanged, its answer altered where it is
    produced, half of its slots left out while reported as solved."""
    from repro.core import gauss_newton as gn

    cell = add_cell(root, f"tiny-8-{fault}", 8, "cohort")
    real = gn.make_cohort_step
    slots = int(cell.cfg["slots"])

    def planted(grid, cfg, **kw):
        step = real(grid, cfg, **kw)
        keep = jax.numpy.arange(slots) < slots // 2

        def faulty(it, g, active, *a):
            new, log = step(it, g, active, *a)
            if fault == "state_unchanged":
                return it, log
            if fault == "answer_altered":
                return gn.Iterate(new.v * 1.01, new.lap2), log
            new, log = step(it, g, active & keep, *a)
            return new, log._replace(gnorm=jax.numpy.where(keep, log.gnorm, 0.0))

        return faulty

    monkeypatch.setattr(gn, "make_cohort_step", planted)
    res = run(cell)
    assert not res["correct"], res["checks"]
