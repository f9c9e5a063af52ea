"""Interpolation kernel microbenchmark (the paper's hot spot, §III-C2).

Measures the oracle's CPU throughput and spot-checks the Pallas kernel in
interpret mode (wall-clock on CPU is meaningless for it; its TPU compile
is pinned by tests/test_tpu_compile.py).

Chip mode times the Pallas kernel alone, on a TPU, at the solver's size:
the padded entries at 128^3, tile (8, 8, 128) and ghost budget 8, C = 1
and 2 channels, on the departure displacement of the paper's v* over one
of 4 time steps at three amplitudes.  Each row gives ms per call, the
window offsets the kernel sweeps per output vreg (reckoned on the host
from the stencil bases; the tricubic stencil itself needs 64), and the
share of those the row and lane sweeps' fixed-width chunks add::

    python -m benchmarks.kernel_microbench --chip

It exits 2 on a machine whose first device is not a TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core.grid import make_grid
from repro.data.synthetic import paper_velocity
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels import tricubic
from repro.kernels.tricubic import tricubic_displace_pallas

CHIP_N, CHIP_HALO, CHIP_TILE, CHIP_DT = 128, 8, (8, 8, 128), 0.25
AMPLITUDES = (0.3, 0.6, 1.0)


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


def vstar_displacement(n: int, amplitude: float, dt: float = CHIP_DT) -> np.ndarray:
    """Departure displacement ``-dt v*(x)`` of the paper's velocity, in grid
    units, (3, n, n, n) float32."""
    v = np.asarray(paper_velocity(make_grid((n,) * 3), amplitude))
    return (-dt * n / (2 * np.pi) * v).astype(np.float32)


def swept_offsets(ib: np.ndarray, tile, lane_chunk: int = 1,
                  row_chunk: int = 1) -> tuple[float, float]:
    """Mean window offsets swept per output vreg (one x1-plane of a tile),
    and the share of them that rounding the x3 and x2 sweeps up to whole
    chunks adds.  Per axis the kernel sweeps the spread of the vreg's
    stencil bases plus the 4 stencil points."""
    _, t2, t3 = tile
    n1, n2, n3 = ib.shape[1:]
    b = ib.reshape(3, n1, n2 // t2, t2, n3 // t3, t3)
    w = (b.max(axis=(3, 5)) - b.min(axis=(3, 5)) + 4).astype(np.float64)
    exact = w[0] * w[1] * w[2]
    padded = w[0] * (-(-w[1] // row_chunk) * row_chunk) * (-(-w[2] // lane_chunk) * lane_chunk)
    return float(exact.mean()), float(padded.sum() / exact.sum() - 1.0)


def _wrap_pad(f, halo):
    return jnp.pad(f, ((0, 0),) + ((halo + 1, halo + 2),) * 3, mode="wrap")


def chip_rows():
    """Time the kernel's padded entries on the chip; one dict per row."""
    n, halo, tile = CHIP_N, CHIP_HALO, CHIP_TILE
    chunks = dict(lane_chunk=tricubic.LANE_CHUNK, row_chunk=tricubic.ROW_CHUNK)
    rng = np.random.default_rng(0)
    rows = []
    for amp in AMPLITUDES:
        disp = jnp.asarray(vstar_displacement(n, amp))
        plan = jax.jit(ref.make_interp_plan)(disp)
        offsets, pad_share = swept_offsets(np.asarray(plan.ib), tile, **chunks)
        for c in (1, 2):
            fpad = _wrap_pad(jnp.asarray(rng.standard_normal((c,) + (n,) * 3), jnp.float32),
                             halo)
            for kind in ("planned", "displace"):
                if kind == "planned":
                    fn, args = tricubic.tricubic_apply_pallas_padded, (fpad, plan.ib, plan.w)
                else:
                    fn, args = tricubic.tricubic_displace_pallas_padded_many, (fpad, disp)
                fn = functools.partial(fn, tile=tile, halo=halo)
                ms = time_fn(fn, *args, iters=20) * 1e3
                rows.append(dict(kind=kind, c=c, amplitude=amp, ms=ms,
                                 offsets_per_vreg=offsets, chunk_pad_share=pad_share,
                                 **chunks))
    return rows


def chip_error():
    """Largest |chip kernel - jnp gather on the host CPU|, C = 2 at
    amplitude 1."""
    disp = vstar_displacement(CHIP_N, 1.0)
    f = np.random.default_rng(1).standard_normal((2,) + (CHIP_N,) * 3).astype(np.float32)
    plan = ref.make_interp_plan(jnp.asarray(disp))
    got = tricubic.tricubic_apply_pallas_padded(_wrap_pad(jnp.asarray(f), CHIP_HALO), plan.ib,
                                                plan.w, tile=CHIP_TILE, halo=CHIP_HALO)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        want = jax.jit(ref.interp_apply)(jnp.asarray(f), ref.make_interp_plan(jnp.asarray(disp)))
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def chip_main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: the first device is {dev.platform}", file=sys.stderr)
        sys.exit(2)
    rows = chip_rows()
    for r in rows:
        emit(f"kernel/tricubic_{r['kind']}_C{r['c']}_amp{r['amplitude']}", r["ms"] * 1e3,
             f"{r['ms']:.3f} ms/call;offsets/vreg={r['offsets_per_vreg']:.1f} (stencil 64);"
             f"chunks (x2 {r['row_chunk']}, x3 {r['lane_chunk']}) pad {r['chunk_pad_share']:.1%}")
    err = chip_error()
    emit("kernel/tricubic_chip_vs_cpu_err", err * 1e6, "max-abs-err-times-1e6")
    print(json.dumps(dict(device=dev.device_kind, n=CHIP_N, halo=CHIP_HALO,
                          tile=CHIP_TILE, rows=rows, max_abs_err=err)))


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chip", action="store_true",
                    help="time the Pallas kernel on the TPU at 128^3 (exits 2 without one)")
    if ap.parse_args().chip:
        chip_main()
    else:
        main()
