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

Layout: the output tile is (T1, T2, T3) with T3 on lanes; on the chip
``T2 % 8 == 0`` and ``T3 % 128 == 0`` (``kernels.ops.pick_tile``).  The VMEM
scratch holds the ghost-extended window ``(C, T1+R-1, T2+R-1, L3)`` with
``L3 = T3+R-1`` rounded up to whole lanes — about 0.5 MB per channel at the
default (8, 8, 128) tile and H=4.  Fields and plan weights are widened to
f32 once on entry; the result is cast back to the field dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import InterpPlan, lagrange_weights

SUBLANES, LANES = 8, 128


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
    """
    _, t2, t3 = tile
    r = 2 * halo + 4
    c, w2, lanes = scratch.shape[0], scratch.shape[2], scratch.shape[3]
    for o in range(r):  # one-hot planes of axes 2 and 3, indexed dynamically
        planes[0, o] = _onehot_plane(rel[1], wts[1], o)
        planes[1, o] = _onehot_plane(rel[2], wts[2], o)
    lo = [jnp.min(x) for x in rel]
    hi = [jnp.max(x) + 4 for x in rel]
    zero = jnp.zeros((t2, t3), jnp.float32)

    def plane_a(a, acc):
        slabs = [scratch[ch, s1 + a] for ch in range(c)]  # (W2, L3)

        def row_b(b, h):
            # rows b .. b+T2 of the window plane: a sublane rotation (loads
            # at a dynamic unaligned sublane offset do not lower)
            shift = (w2 - b) % w2
            rows = [pltpu.roll(x, shift, 0)[:t2] for x in slabs]

            def lane_o(o3, g):
                w = planes[1, o3]
                shift = (lanes - o3) % lanes
                return tuple(g[ch] + w * pltpu.roll(rows[ch], shift, 1)[:, :t3]
                             for ch in range(c))

            g = jax.lax.fori_loop(lo[2], hi[2], lane_o, (zero,) * c)
            w = planes[0, b]
            return tuple(h[ch] + w * g[ch] for ch in range(c))

        h = jax.lax.fori_loop(lo[1], hi[1], row_b, (zero,) * c)
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
            pltpu.VMEM((2, pad + 1, t2, t3), jnp.float32),
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
