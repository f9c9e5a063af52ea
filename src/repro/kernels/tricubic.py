"""Pallas TPU kernel: semi-Lagrangian tricubic interpolation.

The paper measures tricubic interpolation as ~60% of total runtime
(§III-C2: 64 values gathered per point, ~600 flops, compute-to-traffic
ratio O(1) — memory bound on x86) and lists "blocking, prefetching,
vectorization" as future work.  This kernel is the TPU-native realization
of exactly that blocking:

  * The output grid is tiled (T1, T2, T3); for each tile we DMA the
    matching input region *plus a halo* from HBM into a VMEM scratch
    buffer (explicit HBM->VMEM staging = the paper's "prefetching").
    The semi-Lagrangian structure bounds every departure point to
    ``|disp| <= H`` voxels from its home voxel (enforced by the planner,
    see core/planner.py), so one halo of width H+2 covers the whole
    4-point stencil of every query in the tile.
  * TPUs have no hardware gather, so the 4x4x4 stencil gather is recast
    as a dense sweep over the R = 2H+4 window offsets each axis can reach:
    per axis we build R one-hot weight planes (the cubic Lagrange weight of
    each point at each offset, zero off its 4-point stencil) and contract
    the window against them on the VPU — x3 offsets by lane rotations of
    one window row, x2 offsets by sublane-shifted row loads, x1 offsets by
    a loop over window planes.  Every query of a (T2, T3) slice is one
    lane-dense vreg tile, so the whole contraction is exact f32 VPU work
    with no gathers, reshapes or matmul precision loss.
  * Each iteration of a sweep loop costs a latency far above its vector
    work, so the x2 and x3 sweeps run in fixed-width chunks unrolled in the
    trace: ``ROW_CHUNK`` window rows at a time, and for them ``LANE_CHUNK``
    x3 offsets at a time.  A lane chunk rotates the rows' two lane halves
    once by its dynamic base; each of its offsets is then one select and
    one static rotate, accumulated into ``LANE_ACCS`` independent partial
    sums.  The rows and offsets a last chunk adds past the slice's reach
    weigh 0.

Layout: the output tile is (T1, T2, T3) with T3 on lanes; on the chip
``T2 % 8 == 0`` and ``T3 % 128 == 0`` (``kernels.ops.pick_tile``).  The VMEM
scratch holds the ghost-extended window ``(C, T1+R-1, T2+R-1, L3)`` with
``L3 = T3+R-1`` rounded up to whole lanes, and T2+R-1 to whole sublanes.
The solver runs a ghost budget of 8 at 128^3 (R = 20): at the (8, 8, 128)
tile the window is (C, 27, 32, 256) f32, about 0.88 MB per channel (16 on
a 256^3 shard: (C, 43, 48, 256), 2.1 MB).  A second scratch holds the
one-hot planes of axes 2 and 3, (2, R-1+LANE_CHUNK, T2, T3) f32: 0.29 MB at
budget 8, with the LANE_CHUNK-1 zero planes of the chunk tails.  Fields and
plan weights are widened to f32 once on entry; the result is cast back to
the field dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import InterpPlan, lagrange_weights

SUBLANES, LANES = 8, 128
# the x3 offsets and the x2 rows each loop iteration of the contraction
# sweeps, unrolled, and the independent partial sums a channel keeps over a
# row chunk's lane sweep (chosen on a v5e, PERF.md)
LANE_CHUNK, LANE_ACCS, ROW_CHUNK = 16, 2, 4


def _round_up(n, m):
    return -(-n // m) * m


def _onehot_plane(rel, wts, o):
    """Weight of window offset ``o`` for every query: ``wts[k]`` where
    ``rel + k == o`` (``rel`` = halo + stencil base, int32), else 0."""
    plane = jnp.where(rel == o, wts[0], 0.0)
    for k in range(1, 4):
        plane = jnp.where(rel + k == o, wts[k], plane)
    return plane


def _contract_slice(scratch, planes, out_ref, s1, rel, wts, *, tile, halo):
    """Interpolate the C channels of one x1-slice ``s1`` of the output tile.

    ``rel[d]`` (T2, T3) int32 is the window offset of each query's first
    stencil entry along axis d; ``wts[d]`` its 4 Lagrange weights (f32).
    Only the offsets some query of the slice reaches are swept: a smooth
    displacement spans a few offsets per axis, so the cost follows the
    slice's spread of stencil bases, not the halo budget.

    The x2 and x3 sweeps run in chunks unrolled in the trace, so that one
    loop iteration carries much independent work and its latency is paid
    once: ``ROW_CHUNK`` rows of the window plane at a time, and for them
    ``LANE_CHUNK`` x3 offsets at a time.  A lane chunk rotates the rows' two
    lane halves once by its dynamic base; each of its offsets is then one
    select and one static rotate, summed into ``LANE_ACCS`` independent
    partials per channel.  Rows and offsets past the slice's last weigh 0
    (the zeroed tail planes).
    """
    _, t2, t3 = tile
    r = 2 * halo + 4
    c, w2, lanes = scratch.shape[0], scratch.shape[2], scratch.shape[3]
    half = lanes // 2
    # a chunk's shifted rows read lanes up to t3 - 1 + (r - 1) + (LANE_CHUNK - 1)
    assert t3 <= half and t3 + r + LANE_CHUNK - 3 < lanes, (tile, halo, lanes)
    assert ROW_CHUNK <= LANE_CHUNK  # the zero tail below covers both chunks
    zero = jnp.zeros((t2, t3), jnp.float32)
    for o in range(r):  # one-hot planes of axes 2 and 3, indexed dynamically
        planes[0, o] = _onehot_plane(rel[1], wts[1], o)
        planes[1, o] = _onehot_plane(rel[2], wts[2], o)
    for o in range(r, r + LANE_CHUNK - 1):
        planes[0, o] = zero
        planes[1, o] = zero
    lo = [jnp.min(x) for x in rel]
    hi = [jnp.max(x) + 4 for x in rel]
    n_lane = (hi[2] - lo[2] + LANE_CHUNK - 1) // LANE_CHUNK
    n_row = (hi[1] - lo[1] + ROW_CHUNK - 1) // ROW_CHUNK
    lane = jax.lax.broadcasted_iota(jnp.int32, (t2, half), 1)
    rzero = jnp.zeros((ROW_CHUNK, t2, t3), jnp.float32)

    def plane_a(a, acc):
        slabs = [scratch[ch, s1 + a] for ch in range(c)]  # (W2, L3)

        def row_chunk(j, h):
            b = lo[1] + j * ROW_CHUNK
            # rows b+v .. b+v+T2 of the window plane, v < ROW_CHUNK: sublane
            # rotations (loads at a dynamic unaligned sublane offset do not lower)
            rows = [jnp.stack([pltpu.roll(x, (w2 - b - v) % w2, 0)[:t2]
                               for v in range(ROW_CHUNK)]) for x in slabs]

            def lane_chunk(k, g):
                base = lo[2] + k * LANE_CHUNK
                # lanes base .. base + half of the rows, and the half after them
                shift, low = (half - base) % half, lane < half - base
                shifted = []
                for x in rows:
                    x0 = pltpu.roll(x[..., :half], shift, 2)
                    x1 = pltpu.roll(x[..., half:], shift, 2)
                    shifted.append((jnp.where(low, x0, x1), jnp.where(low, x1, x0)))
                g = list(g)
                for u in range(LANE_CHUNK):
                    w = planes[1, base + u]
                    for ch, (x0, x1) in enumerate(shifted):
                        if u:  # x1 fills the u lanes the rotate wraps round
                            x0 = pltpu.roll(jnp.where(lane >= u, x0, x1), half - u, 2)
                        i = ch * LANE_ACCS + u % LANE_ACCS
                        g[i] = g[i] + w * x0[..., :t3]
                return tuple(g)

            g = jax.lax.fori_loop(0, n_lane, lane_chunk, (rzero,) * (c * LANE_ACCS))
            out = []
            for ch in range(c):
                gc = sum(g[ch * LANE_ACCS + 1:(ch + 1) * LANE_ACCS], g[ch * LANE_ACCS])
                out.append(sum((planes[0, b + v] * gc[v] for v in range(ROW_CHUNK)), h[ch]))
            return tuple(out)

        h = jax.lax.fori_loop(0, n_row, row_chunk, (zero,) * c)
        w = _onehot_plane(rel[0], wts[0], a)
        return tuple(acc[ch] + w * h[ch] for ch in range(c))

    acc = jax.lax.fori_loop(lo[0], hi[0], plane_a, (zero,) * c)
    for ch in range(c):
        out_ref[ch, pl.ds(s1, 1), :, :] = acc[ch][None].astype(out_ref.dtype)


def _stage_window(fpad_hbm, scratch, sem, *, tile):
    """DMA the ghost-extended input window of this grid step into VMEM."""
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    w = scratch.shape[1:]
    cp = pltpu.make_async_copy(
        fpad_hbm.at[:, pl.ds(i * tile[0], w[0]), pl.ds(j * tile[1], w[1]),
                    pl.ds(k * tile[2], w[2])],
        scratch,
        sem,
    )
    cp.start()
    cp.wait()


def _kernel_displace(fpad_hbm, disp_ref, out_ref, scratch, planes, sem, *, tile, halo):
    """Stencil bases + weights built in-kernel from the displacement."""
    _stage_window(fpad_hbm, scratch, sem, tile=tile)

    def one_slice(s1, carry):
        rel, wts = [], []
        for d in range(3):
            q = disp_ref[d, s1, :, :]
            ib = jnp.floor(q)
            rel.append(ib.astype(jnp.int32) + halo)
            wts.append(list(lagrange_weights(q - ib)))
        _contract_slice(scratch, planes, out_ref, s1, rel, wts, tile=tile, halo=halo)
        return carry

    jax.lax.fori_loop(0, tile[0], one_slice, 0)


def _kernel_planned(fpad_hbm, ib_ref, w_ref, out_ref, scratch, planes, sem, *, tile, halo):
    """Planned variant: stencil bases + weights arrive precomputed (InterpPlan
    blocks), so the per-point floor and weight polynomials are skipped."""
    _stage_window(fpad_hbm, scratch, sem, tile=tile)

    def one_slice(s1, carry):
        rel = [ib_ref[d, s1, :, :] + halo for d in range(3)]
        wts = [[w_ref[d, k, s1, :, :] for k in range(4)] for d in range(3)]
        _contract_slice(scratch, planes, out_ref, s1, rel, wts, tile=tile, halo=halo)
        return carry

    jax.lax.fori_loop(0, tile[0], one_slice, 0)


def _pallas(kern, fpad, operands, *, tile, halo, interpret):
    """Shared pallas_call plumbing: ``fpad`` (C, ghost-extended) + per-point
    operand blocks whose trailing three axes tile like the output."""
    pad = 2 * halo + 3
    c, dtype = fpad.shape[0], fpad.dtype
    n1, n2, n3 = (s - pad for s in fpad.shape[1:])
    t1, t2, t3 = tile
    assert n1 % t1 == 0 and n2 % t2 == 0 and n3 % t3 == 0, ((n1, n2, n3), tile)
    # the staged window is rounded up to whole (8, 128) tiles — DMA slices of
    # tiled memory must be tile-aligned — so the hi side gets zero planes that
    # keep the last window in bounds (never read by any stencil)
    window = (t1 + pad, _round_up(t2 + pad, SUBLANES), _round_up(t3 + pad, LANES))
    extra = [(0, 0), (0, 0)] + [(0, w - t - pad) for w, t in zip(window[1:], tile[1:])]
    fpad = jnp.pad(fpad.astype(jnp.float32), extra)

    def spec(x):
        lead = x.ndim - 3
        return pl.BlockSpec(x.shape[:lead] + tuple(tile),
                            lambda i, j, k: (0,) * lead + (i, j, k))

    out = pl.pallas_call(
        functools.partial(kern, tile=tile, halo=halo),
        grid=(n1 // t1, n2 // t2, n3 // t3),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] + [spec(x) for x in operands],
        out_specs=pl.BlockSpec((c,) + tuple(tile), lambda i, j, k: (0, i, j, k)),
        out_shape=jax.ShapeDtypeStruct((c, n1, n2, n3), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((c,) + window, jnp.float32),
            pltpu.VMEM((2, pad + LANE_CHUNK, t2, t3), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(fpad, *operands)
    return out.astype(dtype)


@functools.partial(jax.jit, static_argnames=("tile", "halo", "interpret"))
def tricubic_displace_pallas_padded_many(
    fpad: jnp.ndarray,
    disp: jnp.ndarray,
    *,
    tile: tuple[int, int, int] = (8, 8, 128),
    halo: int = 4,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched kernel entry for an ALREADY ghost-extended stack.

    ``fpad`` (C, N1+2H+3, N2+2H+3, N3+2H+3) has ``halo+1`` planes below and
    ``halo+2`` above each axis — the layout produced by one stacked
    ``jnp.pad(mode="wrap")`` or by the single batched ghost exchange of
    ``repro.dist.halo``; ``disp`` (3, N1, N2, N3) is shared by all channels.
    """
    return _pallas(_kernel_displace, fpad, (disp.astype(jnp.float32),),
                   tile=tile, halo=halo, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "halo", "interpret"))
def tricubic_apply_pallas_padded(
    fpad: jnp.ndarray,
    ib: jnp.ndarray,
    w: jnp.ndarray,
    *,
    tile: tuple[int, int, int] = (8, 8, 128),
    halo: int = 4,
    interpret: bool = False,
) -> jnp.ndarray:
    """Planned batched kernel entry: ``fpad`` (C, ghost-extended), plus the
    ``InterpPlan`` operator arrays ``ib`` (3, N..) / ``w`` (3, 4, N..)."""
    return _pallas(_kernel_planned, fpad,
                   (ib.astype(jnp.int32), w.astype(jnp.float32)),
                   tile=tile, halo=halo, interpret=interpret)


def _wrap_pad(fields: jnp.ndarray, halo: int) -> jnp.ndarray:
    lo, hi = halo + 1, halo + 2
    return jnp.pad(fields, ((0, 0), (lo, hi), (lo, hi), (lo, hi)), mode="wrap")


def tricubic_displace_pallas_many(
    fields: jnp.ndarray,
    disp: jnp.ndarray,
    *,
    tile: tuple[int, int, int] = (8, 8, 128),
    halo: int = 4,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched ``fields`` (C, N1,N2,N3) at x + disp, |disp| <= halo.

    Wrap-around periodicity is materialized once by pre-padding the stack
    by (halo+1, halo+2) planes per axis (mode="wrap"); afterwards all
    kernel addressing is local and static.
    """
    return tricubic_displace_pallas_padded_many(
        _wrap_pad(fields, halo), disp, tile=tile, halo=halo, interpret=interpret
    )


def tricubic_displace_pallas(
    field: jnp.ndarray,
    disp: jnp.ndarray,
    *,
    tile: tuple[int, int, int] = (8, 8, 128),
    halo: int = 4,
    interpret: bool = False,
) -> jnp.ndarray:
    """Evaluate ``field`` (N1, N2, N3) at ``x + disp`` (grid units),
    |disp| <= halo: the C=1 case of ``tricubic_displace_pallas_many``."""
    return tricubic_displace_pallas_many(
        field[None], disp, tile=tile, halo=halo, interpret=interpret
    )[0]


def tricubic_apply_pallas(
    fields: jnp.ndarray,
    plan: InterpPlan,
    *,
    tile: tuple[int, int, int] = (8, 8, 128),
    halo: int = 4,
    interpret: bool = False,
) -> jnp.ndarray:
    """Planned batched apply (periodic wrap materialized by pre-padding)."""
    return tricubic_apply_pallas_padded(
        _wrap_pad(fields, halo), plan.ib, plan.w,
        tile=tile, halo=halo, interpret=interpret,
    )
