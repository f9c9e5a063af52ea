#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's numbers over
many seeds and the lower-precision control's, in one process on the chip.

    python3 bench/readings.py --workload claire128-serve --seeds 1-12 \
        --control-seeds 13-15 --out chiprun_out/readings.json

For each seed it builds the seed's inputs, registers one batch through the
timed path (the warm-up is paid once per process) and compares the
registrations a run would check with the float64 reference.  The
control is the program's own ``field_dtype="bfloat16"`` path.  The
benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as harness  # noqa: E402

CONTROL = "bfloat16"  # the nearest precision below the configuration's float32


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def readings(cell, seed_list, devices, field_dtype=None, log=harness.say) -> list[dict]:
    """One row per registration a run would check: its status, counts and
    every compared number.  The control's registrations are compared
    whether or not they claim convergence."""
    import numpy as np

    from bench import reference
    from bench.generate import Traffic

    system = harness.System(cell.cfg, devices, field_dtype=field_dtype)
    out = []
    for n, seed in enumerate(seed_list):
        traffic = Traffic(cell.cfg, cell.mix, seed, place=system.place)
        if n == 0:
            system.warm_up(traffic)
        pairs = traffic.batch(1)
        t = time.perf_counter()
        regs = system.run_batch(pairs, tag=seed)
        batch_s = time.perf_counter() - t
        pool = [k for k, r in enumerate(regs)
                if r["status"] == "converged" or field_dtype is not None]
        for k in [pool[i] for i in harness.sample_of(seed, len(pool), cell.cfg)]:
            reg = {n_: (np.asarray(v) if hasattr(v, "shape") else v) for n_, v in regs[k].items()}
            row = {"seed": seed, "subject": k, "status": reg["status"],
                   "newton_iters": reg["newton_iters"], "matvecs": reg["matvecs"],
                   "batch_s": batch_s, "field_dtype": field_dtype,
                   "statuses": [r["status"] for r in regs],
                   "carry_err": reference.carried_image_error(reg["v"], reg["lap2"])}
            row.update(harness.check(reg, [np.asarray(a) for a in pairs[k]], cell.cfg))
            log(json.dumps(row))
            out.append(row)
        del regs, pairs
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    harness.enable_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("readings: needs the cell's TPU chips", file=sys.stderr)
        return 2
    rows = readings(cell, seeds(args.seeds), devices)
    if args.control_seeds:
        rows += readings(cell, seeds(args.control_seeds), devices, field_dtype=CONTROL)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
