"""Work of one interpolation call, from its points, fields and dtypes only.

A tricubic Lagrange interpolation of ``C`` fields at ``P`` points needs,
whatever implements it: each field read once and written once, and the
3-component departure displacement read once; per point the separable
weights (3 axes x 4 weights, 16 flops an axis) and per field and point the
64-point contraction done axis by axis (2 x (64 + 16 + 4) flops).  How a
kernel sweeps, pads or plans does not enter.
"""
from __future__ import annotations

import math
import re

WEIGHT_FLOPS = 3 * 16
CONTRACT_FLOPS = 2 * (64 + 16 + 4)
DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2}
SHAPE = re.compile(r"\b(f64|f32|bf16|f16)\[([0-9,]+)\]")


def interp_work(points: int, fields: int, field_bytes: int = 4, disp_bytes: int = 4):
    """(flops, bytes) of interpolating ``fields`` fields at ``points`` points."""
    flops = points * (WEIGHT_FLOPS + fields * CONTRACT_FLOPS)
    nbytes = points * (2 * fields * field_bytes + 3 * disp_bytes)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict):
    """The roofline's least time for the work, and which bound sets it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")


def call_shape(text: str):
    """(dtype, dims) of the first array shape in an op's HLO text — the
    output of an interpolation call is (C, N1, N2, N3)."""
    m = SHAPE.search(text)
    if m is None:
        return None
    return m.group(1), [int(d) for d in m.group(2).split(",") if d]


def call_work(text: str):
    """(flops, bytes) of the interpolation call whose HLO text is ``text``,
    or None where no (..., N1, N2, N3) output shape can be read."""
    shape = call_shape(text)
    if shape is None or len(shape[1]) < 3:
        return None
    dtype, dims = shape
    return interp_work(math.prod(dims[-3:]), math.prod(dims[:-3]), DTYPE_BYTES[dtype])
