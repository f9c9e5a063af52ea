"""Halo-exchange tricubic interpolation (paper §III-C2, Alg. 1).

The semi-Lagrangian solver evaluates fields at departure points that the
planner bounds to ``|disp| <= halo`` voxels from their home voxel
(``repro.core.planner.required_halo``).  On a 2-D pencil mesh each device
therefore only needs its own block plus a ghost layer wide enough to cover
``halo`` plus the tricubic stencil's (-1..+2) reach — the paper's Alg. 1
scatter phase, realized here as neighbor-block ``lax.ppermute`` hops
inside ``shard_map`` instead of MPI_Alltoallv.

Ghost widths: a query ``q = i + d`` with ``|d| < halo`` touches stencil
rows ``floor(q)-1 .. floor(q)+2``, i.e. ``halo+1`` cells below the block
and ``halo+2`` above.  When the ghost layer is wider than the shard
itself (claire-brain's halo=8 on 16-wide production shards, or halo=9 on
4-wide test shards) the exchange takes ``ceil(width / shard_width)``
ppermute hops per direction — whole neighbor blocks are forwarded
ring-style and the overhang is trimmed.  The unsharded third axis wraps
locally.  After the exchange, interpolation is embarrassingly local and
reuses the ``kernels/ref.py`` oracle arithmetic verbatim, so the
distributed path is bit-comparable to the single-device one.

Batched multi-field contract: the interp built here accepts ``fields``
with leading channel dims (C, N1, N2, N3).  The whole C-stack rides ONE
ghost-exchange sequence per call — the per-direction ``ppermute`` count is
independent of C, a C x cut in collective-latency count versus C looped
scalar calls (pinned by ``tests/test_dist_interp.py``, measured by
``benchmarks`` suite ``interp``).  It also implements the plan protocol of
``core.semilag``: ``make_plan(disp)`` precomputes the per-point stencil
operators (elementwise in ``disp`` — sharding-preserving, no collectives)
and ``apply_plan(fields, plan)`` interpolates against the cached weights,
so every transport of a Newton iteration skips the per-call weight
construction.

Cohort contract: per-subject displacements ``(S, 3, N..)`` (or a cohort
``InterpPlan``) pair with fields carrying the subject axis at ``-4`` —
``(C, S, N1, N2, N3)``.  The whole (C, S) stack still rides ONE
ghost-exchange sequence per call, so the per-call collective count is
independent of the cohort size — the amortization ``gn.solve_cohort``
is built on (counted-collective pin in ``tests/test_cohort.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.core.grid import Grid
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.tricubic import (
    tricubic_apply_pallas_padded,
    tricubic_displace_pallas_padded_many,
)
from repro.launch.mesh import mesh_axes_size


def _wrap_pad(x: jnp.ndarray, lo: int, hi: int, axis: int) -> jnp.ndarray:
    """Periodic pad along an unsharded local axis (index arithmetic, so the
    pad may exceed the axis length)."""
    n = x.shape[axis]
    idx = jnp.arange(-lo, n + hi) % n
    return jnp.take(x, idx, axis=axis)


def _neighbor_blocks(x: jnp.ndarray, name, p: int, hops: int, from_left: bool):
    """Blocks of the ``hops`` nearest ring neighbors in one direction.

    ``from_left=True`` returns ``[block_{i-1}, block_{i-2}, ...]`` at device
    ``i`` (periodic); each hop forwards the previously received block.
    """
    step = -1 if from_left else 1
    perm = [((j + step) % p, j) for j in range(p)]
    out, cur = [], x
    for _ in range(hops):
        cur = lax.ppermute(cur, name, perm)
        out.append(cur)
    return out


def _exchange_axis(x: jnp.ndarray, name, p: int, lo: int, hi: int, axis: int):
    """Extend ``x`` by ``lo``/``hi`` ghost cells along a sharded local axis.

    Leading (channel) dims of ``x`` ride along: the ppermute count per
    direction depends only on the ghost width, never on the stack size.
    """
    n = x.shape[axis]
    if p == 1:
        return _wrap_pad(x, lo, hi, axis)
    kl, kh = -(-lo // n), -(-hi // n)
    # single hop (the common case): permute only the ghost strip; multi-hop
    # forwards whole blocks, since later hops need the full previous block
    send_l = x if kl > 1 else lax.slice_in_dim(x, n - lo, n, axis=axis)
    send_r = x if kh > 1 else lax.slice_in_dim(x, 0, hi, axis=axis)
    left = _neighbor_blocks(send_l, name, p, hops=kl, from_left=True)
    right = _neighbor_blocks(send_r, name, p, hops=kh, from_left=False)
    lcat = jnp.concatenate(list(reversed(left)), axis=axis)
    rcat = jnp.concatenate(right, axis=axis)
    return jnp.concatenate(
        [
            lax.slice_in_dim(lcat, lcat.shape[axis] - lo, lcat.shape[axis], axis=axis),
            x,
            lax.slice_in_dim(rcat, 0, hi, axis=axis),
        ],
        axis=axis,
    )


def _exchange_ghosts(f: jnp.ndarray, *, a1, a2, p1, p2, lo, hi) -> jnp.ndarray:
    """One full ghost exchange of a local block (..., n1l, n2l, n3)."""
    nd = f.ndim
    fp = _exchange_axis(f, a1, p1, lo, hi, axis=nd - 3)
    fp = _exchange_axis(fp, a2, p2, lo, hi, axis=nd - 2)
    return _wrap_pad(fp, lo, hi, axis=nd - 1)


def _interp_local_many(f, d, *, a1, a2, p1, p2, lo, hi, kernel):
    """Batched per-device body: ``f`` (C, n1l, n2l, n3) rides ONE exchange.

    Scalar fields go through here too (C=1, reshaped by the dispatcher) —
    one exchange/dispatch implementation for every arity.

    ``kernel`` (a ``kernels.ops.Resolved``) with method "pallas" dispatches
    the per-shard interpolation to the VMEM-blocked Pallas kernel
    (``kernels/tricubic.py``): the ghost-extended block IS the kernel's
    padded-field layout (``halo+1`` planes below, ``halo+2`` above), so the
    exchange and the kernel compose with no copy.
    """
    fp = _exchange_ghosts(f, a1=a1, a2=a2, p1=p1, p2=p2, lo=lo, hi=hi)
    if kernel.method == "pallas":
        return tricubic_displace_pallas_padded_many(
            fp, d, tile=kernel.tile, halo=lo - 1, interpret=kernel.interpret
        )
    return ref.interp_apply_padded(fp, ref.make_interp_plan(d), lo)


def _apply_local_many(f, ib, w, *, a1, a2, p1, p2, lo, hi, kernel):
    """Planned batched body: precomputed local operators, one exchange."""
    fp = _exchange_ghosts(f, a1=a1, a2=a2, p1=p1, p2=p2, lo=lo, hi=hi)
    if kernel.method == "pallas":
        return tricubic_apply_pallas_padded(
            fp, ib, w, tile=kernel.tile, halo=lo - 1, interpret=kernel.interpret
        )
    need = jnp.zeros((), jnp.float32)  # bound enforced by the checked wrapper
    return ref.interp_apply_padded(fp, ref.InterpPlan(ib=ib, w=w, halo_need=need), lo)


def _interp_local_cohort(f, d, *, a1, a2, p1, p2, lo, hi, kernel):
    """Cohort per-device body: ``f`` (C, S, n1l, n2l, n3) against per-subject
    displacements ``d`` (S, 3, n1l, n2l, n3).

    The ENTIRE (C, S) stack rides the one ghost-exchange sequence — the
    ppermute count is independent of both the channel count and the cohort
    size, which is the collective-amortization the cohort solver banks on.
    The per-shard interpolation is the ``kernels/ref.py`` cohort gather
    (``interp_apply_padded`` vmaps each subject against its own operators);
    the Pallas kernel keeps its single-subject scope.
    """
    fp = _exchange_ghosts(f, a1=a1, a2=a2, p1=p1, p2=p2, lo=lo, hi=hi)
    return ref.interp_apply_padded(fp, ref.make_interp_plan(d), lo)


def _apply_local_cohort(f, ib, w, *, a1, a2, p1, p2, lo, hi, kernel):
    """Planned cohort body: precomputed per-subject operators, one exchange."""
    fp = _exchange_ghosts(f, a1=a1, a2=a2, p1=p1, p2=p2, lo=lo, hi=hi)
    need = jnp.zeros((), jnp.float32)  # bound enforced by the checked wrapper
    return ref.interp_apply_padded(fp, ref.InterpPlan(ib=ib, w=w, halo_need=need), lo)


def make_halo_interp(grid: Grid, mesh, axes=("data", "model"), halo: int = 4,
                     method: str = "auto", plan_dtype=None):
    """Build the distributed ``interp`` callable (batched + plan protocol).

    Plugs into every ``interp=`` slot of ``repro.core.semilag`` /
    ``repro.core.planner``: ``fields`` is ``(..., N1, N2, N3)`` sharded
    ``P(a1, a2, None)`` over the trailing space axes (leading channel dims
    replicated as a stack), ``disp`` a ``(3, N1, N2, N3)`` grid-unit
    displacement sharded ``P(None, a1, a2, None)`` with ``|disp| < halo``.
    ``method`` picks the per-shard kernel for the shard's block shape
    (``kernels.ops.resolve``: "auto" = Pallas on TPU, gather elsewhere).

    The returned callable carries ``make_plan`` / ``apply_plan`` so the
    solver's plan-once/apply-many path works on the mesh: plan construction
    is elementwise (stays sharded, no collectives) and the planned apply
    runs the same single ghost-exchange sequence per call.  ``plan_dtype``
    packs the cached plan weights (``jnp.bfloat16`` halves the plan's HBM
    footprint per shard; the per-shard contraction still upcasts to f32 —
    see ``ref.make_interp_plan``).  The three entries trace under the
    named scopes of ``kernels.ops.Interp``: ``interp.displace``,
    ``interp.plan`` and ``interp.apply``.
    """
    a1, a2 = tuple(axes)
    p1, p2 = mesh_axes_size(mesh, a1), mesh_axes_size(mesh, a2)
    n1, n2, n3 = grid.shape
    if n1 % p1 or n2 % p2:
        raise ValueError(f"grid {grid.shape} not divisible by pencil mesh ({p1},{p2})")
    kernel = kops.resolve(method, (n1 // p1, n2 // p2, n3))
    kw = dict(a1=a1, a2=a2, p1=p1, p2=p2, lo=halo + 1, hi=halo + 2, kernel=kernel)
    smkw = dict(mesh=mesh, check_vma=False)
    s_stack = P(None, a1, a2, None)
    s_w = P(None, None, a1, a2, None)
    sm4 = shard_map(partial(_interp_local_many, **kw), in_specs=(s_stack, s_stack),
                    out_specs=s_stack, **smkw)
    sm_apply = shard_map(partial(_apply_local_many, **kw), in_specs=(s_stack, s_stack, s_w),
                         out_specs=s_stack, **smkw)
    # cohort variants: a subjects axis rides between the channel stack and
    # space — (C, S, n1, n2, n3) fields against (S, 3, n..) displacements /
    # (S, 3, 4, n..) plan weights, all replicated over the leading dims
    s_coh = P(None, None, a1, a2, None)
    s_coh_w = P(None, None, None, a1, a2, None)
    sm5 = shard_map(partial(_interp_local_cohort, **kw), in_specs=(s_coh, s_coh),
                    out_specs=s_coh, **smkw)
    sm_apply5 = shard_map(partial(_apply_local_cohort, **kw), in_specs=(s_coh, s_coh, s_coh_w),
                          out_specs=s_coh, **smkw)

    @telemetry.annotate("interp.displace")
    def interp(field, disp):
        if disp.ndim == 5:  # cohort: per-subject displacements
            lead = field.shape[:-4]
            out = sm5(field.reshape((-1,) + field.shape[-4:]), disp)
            return out.reshape(lead + out.shape[-4:])
        lead = field.shape[:-3]
        out = sm4(field.reshape((-1,) + field.shape[-3:]), disp)
        return out.reshape(lead + out.shape[-3:])

    @telemetry.annotate("interp.apply")
    def apply_plan(fields, plan: ref.InterpPlan):
        if plan.ib.ndim == 5:  # cohort plan: per-subject operators
            lead = fields.shape[:-4]
            out = sm_apply5(fields.reshape((-1,) + fields.shape[-4:]), plan.ib, plan.w)
            return out.reshape(lead + out.shape[-4:])
        lead = fields.shape[:-3]
        out = sm_apply(fields.reshape((-1,) + fields.shape[-3:]), plan.ib, plan.w)
        return out.reshape(lead + out.shape[-3:])

    @telemetry.annotate("interp.plan")
    def make_plan(disp):
        return ref.make_interp_plan(disp, dtype=plan_dtype)

    interp.kernel = kernel
    interp.make_plan = make_plan
    interp.apply_plan = apply_plan
    return interp


# --------------------------------------------------------------------------- #
# dynamic halo budget (ROADMAP): the ghost exchange is only correct while
# every departure point stays within ``halo`` voxels of its home voxel
# (``repro.core.planner.required_halo``'s bound).  A line-search step that
# overshoots would silently read ring-wrapped garbage from the local block;
# the checked wrapper turns that into an explicit runtime branch.
# --------------------------------------------------------------------------- #
def make_checked_interp(halo_interp, mesh, axes, halo: int, on_overflow: str = "error"):
    """Wrap a halo interp with a per-call displacement-bound check.

    ``on_overflow``:
      * "error"  — cheap default: the output is NaN-poisoned and a debug
        message printed when ``ceil(max|disp|) > halo``; NaNs surface in the
        line search / convergence test instead of a silently wrong field.
      * "gather" — correct-but-slow fallback: a ``lax.cond`` switches to the
        global ``kernels/ref.py`` gather (XLA all-gathers the field), so the
        iteration stays exact at the cost of one global collective.

    On the planned path the bound comes for free off the cached
    ``InterpPlan.halo_need`` (one max-reduction per Newton iteration, paid
    at plan-build time, instead of one per interp call).
    """
    a1, a2 = tuple(axes)
    budget = jnp.float32(halo)

    def out_sharding(ndim):
        lead = (None,) * (ndim - 3)
        return NamedSharding(mesh, P(*lead, a1, a2, None))

    def _record_overflow(n):
        # host-side: count the violation and render the legacy warning line
        # (echo keeps the printed diagnostic; sinks additionally get a
        # ``halo_budget_exceeded`` counter event with the offending bound)
        telemetry.counter(
            "halo_budget_exceeded", echo=True,
            required=float(n), budget=halo, mode="gather",
        )

    def warn_if(ok, need):
        lax.cond(
            ok,
            lambda n: None,
            lambda n: jax.debug.callback(_record_overflow, n),
            need,
        )

    @telemetry.annotate("interp.displace")
    def checked(field, disp):
        need = jnp.ceil(kops.max_displacement(disp))
        if on_overflow != "gather":
            return kops.poison_beyond_halo(halo_interp(field, disp), need, halo)
        ok = need <= budget
        warn_if(ok, need)
        return lax.cond(
            ok,
            halo_interp,
            lambda f, d: lax.with_sharding_constraint(
                ref.tricubic_displace_many(f, d), out_sharding(field.ndim)
            ),
            field,
            disp,
        )

    @telemetry.annotate("interp.apply")
    def checked_apply(fields, plan: ref.InterpPlan):
        if on_overflow != "gather":
            return kops.poison_beyond_halo(halo_interp.apply_plan(fields, plan),
                                           plan.halo_need, halo)
        ok = plan.halo_need <= budget
        warn_if(ok, plan.halo_need)
        # ref.interp_apply wraps by global index arithmetic — exact for
        # any displacement, so it is the planned gather fallback
        return lax.cond(
            ok,
            halo_interp.apply_plan,
            lambda f, p: lax.with_sharding_constraint(
                ref.interp_apply(f, p), out_sharding(fields.ndim)
            ),
            fields,
            plan,
        )

    checked.kernel = halo_interp.kernel
    checked.make_plan = halo_interp.make_plan
    checked.apply_plan = checked_apply
    return checked
