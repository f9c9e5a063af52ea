"""Benchmark harness — one module per paper table (+ kernel + LM roofline).

    PYTHONPATH=src python -m benchmarks.run                    # all
    PYTHONPATH=src python -m benchmarks.run table5             # one
    PYTHONPATH=src python -m benchmarks.run --suite multilevel # same, flag form
                                             (writes BENCH_multilevel.json)

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import subprocess
import sys
import traceback

from repro import telemetry
from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (
    autotune_suite,
    blocks_suite,
    cohort_suite,
    fft_suite,
    interp_suite,
    kernel_microbench,
    lm_roofline,
    multilevel_c2f,
    resilience_suite,
    table1_scaling,
    table3_incompressible,
    table5_beta,
)

TABLES = {
    "table1": table1_scaling.main,
    "table3": table3_incompressible.main,
    "table5": table5_beta.main,
    "kernel": kernel_microbench.main,
    "interp": interp_suite.main,
    "fft": fft_suite.main,
    "lm_roofline": lm_roofline.main,
    "multilevel": multilevel_c2f.main,
    "cohort": cohort_suite.main,
    "autotune": autotune_suite.main,
    "blocks": blocks_suite.main,
    "resilience": resilience_suite.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("suites", nargs="*", help=f"subset to run: {list(TABLES)}")
    ap.add_argument("--suite", action="append", default=[], choices=list(TABLES),
                    help="suite to run (repeatable); combined with positionals")
    args = ap.parse_args()
    enable_compile_cache()
    which = list(args.suites) + list(args.suite) or list(TABLES)
    unknown = [w for w in which if w not in TABLES]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; choose from {list(TABLES)}")

    failed = []
    if len(which) > 1:
        # one process per suite: the fft/interp/autotune suites start a CPU
        # child before their own JAX work, which an earlier suite in this
        # process would otherwise have done already (a chip has one owner)
        for name in which:
            proc = subprocess.run([sys.executable, "-m", "benchmarks.run", name])
            if proc.returncode != 0:
                failed.append(name)
    else:
        # REPRO_TRACE=path.jsonl captures every bench row as telemetry events
        telemetry.configure_from_env()
        print("name,us_per_call,derived")
        try:
            TABLES[which[0]]()
        except Exception:
            traceback.print_exc()
            failed.append(which[0])
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
