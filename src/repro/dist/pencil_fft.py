"""2-D pencil-decomposed distributed FFT (paper §III-C1, Fig. 4).

The paper parallelizes its spectral operators with AccFFT's 2-D pencil
decomposition: the ``N1 x N2 x N3`` grid is split over a ``p1 x p2``
process grid, each 1-D transform runs on a locally-complete axis, and two
all-to-all transposes re-pencil the data between axis passes.  This module
is the same algorithm as a JAX SPMD program: ``shard_map`` gives each
device its pencil, ``lax.all_to_all`` performs the transposes, and XLA
overlaps them with the surrounding elementwise work.

Layouts (per device, global shape ``(B, N1, N2, N3)``):

    real space   (B, N1/p1, N2/p2, N3)        P(None, a1, a2, None)
    after pass 1 (B, N1/p1, N2,    N3/p2)     transpose over a2
    after pass 2 (B, N1,    N2/p1, N3/p2)     transpose over a1
    k space      (B, N1,    N2/p1, N3/p2)     P(None, None, a1, a2)

All three passes are complex-to-complex.  A c2c transform (instead of the
single-device ``rfftn``) keeps every transposed axis length divisible by
the pencil sizes for any valid mesh (an r2c last axis of ``N3/2 + 1``
modes is generally not), at the cost of 2x redundant spectrum storage.
The bandwidth is won back with the classic packing trick on BOTH sides:

* ``inv_packed``: two real-destined spectra ``Fa, Fb`` ride one inverse
  transform as ``Fa + i Fb``, since ``ifft`` is linear and ``a, b`` real
  means ``a = Re ifft``, ``b = Im ifft``.
* ``fwd_packed``: two *real* fields ride one forward transform as
  ``a + i b``; Hermitian symmetry of real spectra unpacks them via
  ``Fa = (Z + conj(Z(-k)))/2``, ``Fb = -i (Z - conj(Z(-k)))/2``.  The
  frequency reversal ``Z(-k)`` is a flip+roll of the sharded spectrum,
  which GSPMD lowers to shard-reversing collective-permutes — far cheaper
  than the all-to-all transposes the second transform would have cost.
  An odd trailing field rides the SAME shard_map call as an unpaired c2c
  (mirroring ``inv_packed``), so every packed ride is exactly one
  transform program — one all-to-all pair per direction, never two.

``SpectralOps`` probes for these via the ``packed`` attribute and routes
every batched real(-destined) transform (gradients of time series, Leray,
``div``, coalesced ``SpectralBatch`` rides) through them — halving the
pencil all-to-all bytes on each routed side.

Communication/computation pipelining (the AccFFT overlap trick, also the
multi-GPU CLAIRE optimization, arXiv:2008.12820): ``PencilFFT(chunk=...)``
splits the flattened batch axis *inside* the shard_map body into chunks
and transforms them as independent dataflow chains.  The all-to-all of
chunk ``i`` has no dependence on the local 1-D FFTs of chunk ``i+1``, so
XLA's async collective scheduler double-buffers them — the transpose of
one chunk hides behind the compute of the next.  ``chunk="auto"`` sizes
chunks off the per-shard pencil footprint (pipelining only pays once a
chunk's transpose is bandwidth- rather than latency-bound); chunking is
exact — the chunked program computes bit-identical results to the
unchunked one for every mesh layout, batch size, and chunk remainder.

Mesh axis entries may be tuples (e.g. ``(("pod", "data"), "model")``) so a
multi-pod mesh can fold two device axes into one pencil dimension.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.core.grid import Grid
from repro.launch.mesh import mesh_axes_size, validate_mesh_for_grid

# auto-chunk target: per-shard bytes one chunk moves through each
# all-to-all.  Big enough that a chunk's transpose is bandwidth-bound
# (pipelining overlaps it with the next chunk's FFTs), small enough that
# a large batched ride splits into >= 2 overlappable stages.
AUTO_CHUNK_TARGET_BYTES = 8 << 20


def resolve_chunk(chunk, grid_shape, p1: int, p2: int) -> int:
    """Fields-per-chunk for the pipelined transform; 0 disables chunking.

    ``"auto"`` targets ``AUTO_CHUNK_TARGET_BYTES`` of complex64 (8 B/point)
    per-shard pencil data per chunk: at production shards that is chunk=16
    at 256^3 on 256 chips (0.5 MB/field) down to chunk=2 at 512^3
    (4 MB/field — near-maximal overlap); at toy shards the chunk swallows
    any realistic batch and the path degrades gracefully to the unchunked
    single ride.
    """
    if chunk in (None, 0):
        return 0
    if chunk == "auto":
        per_field = 8 * int(np.prod(grid_shape)) // max(p1 * p2, 1)
        return max(1, AUTO_CHUNK_TARGET_BYTES // max(per_field, 1))
    c = int(chunk)
    if c < 1:
        raise ValueError(f"chunk must be >= 1, 'auto', or None; got {chunk!r}")
    return c


def _fwd_one(x, *, a1, a2, p1, p2):
    """Per-device pencil forward: 3 local 1-D c2c passes + 2 transposes."""
    x = jnp.fft.fft(x, axis=-1)
    if p2 > 1:  # gather N2, scatter N3 over the second pencil axis
        x = lax.all_to_all(x, a2, split_axis=3, concat_axis=2, tiled=True)
    x = jnp.fft.fft(x, axis=-2)
    if p1 > 1:  # gather N1, scatter N2 over the first pencil axis
        x = lax.all_to_all(x, a1, split_axis=2, concat_axis=1, tiled=True)
    return jnp.fft.fft(x, axis=-3)


def _inv_one(s, *, a1, a2, p1, p2):
    """Per-device pencil inverse: exact reversal of ``_fwd_one``."""
    s = jnp.fft.ifft(s, axis=-3)
    if p1 > 1:
        s = lax.all_to_all(s, a1, split_axis=1, concat_axis=2, tiled=True)
    s = jnp.fft.ifft(s, axis=-2)
    if p2 > 1:
        s = lax.all_to_all(s, a2, split_axis=2, concat_axis=3, tiled=True)
    return jnp.fft.ifft(s, axis=-1)


def _pipelined(one, x, *, chunk, p1, p2, **kw):
    """Software-pipelined transform: independent per-chunk dataflow chains.

    The unrolled chunk loop IS the pipeline — chunk ``i``'s all-to-all and
    chunk ``i+1``'s local FFTs share no data, so the async collective
    scheduler issues the transpose of one chunk under the compute of the
    next (double buffering falls out of the dependence structure; no
    manual send/recv choreography needed).  The trailing remainder chunk
    is simply smaller — results are identical to the unchunked call.
    """
    b = x.shape[0]
    if not chunk or b <= chunk or (p1 == 1 and p2 == 1):
        return one(x, p1=p1, p2=p2, **kw)
    parts = [one(x[i : i + chunk], p1=p1, p2=p2, **kw) for i in range(0, b, chunk)]
    return jnp.concatenate(parts, axis=0)


class PencilFFT:
    """Drop-in ``FFTBackend`` running the paper's pencil FFT on a mesh.

    Same interface as ``repro.core.spectral.LocalFFT`` (``fwd``/``inv`` and
    the ``k``/``kd``/``ksq``/``ksq_d`` wavenumber grids), so every operator
    in ``SpectralOps`` works unmodified; the wavenumber grids use the full
    (non-rfft) last axis to match the c2c spectrum layout.

    ``chunk``: fields per pipelined chunk inside the shard_map body
    (``None`` = single ride, ``"auto"`` = footprint heuristic, int = fixed).

    ``field_dtype``: storage dtype of the REAL fields the inverse side
    returns (default ``grid.dtype``); e.g. ``jnp.bfloat16`` halves the
    resident footprint of inverse-transformed stacks.  The transform
    itself stays complex64 — forward inputs are upcast, so precision is
    lost only at the real-space store (the ``repro.autotune``
    mixed-precision knob, threaded here by ``DistContext``).
    """

    def __init__(
        self, grid: Grid, mesh, axes=("data", "model"), packed: bool = True, chunk=None,
        field_dtype=None,
    ):
        validate_mesh_for_grid(mesh, grid.shape, axes)
        self.grid = grid
        self.mesh = mesh
        self.axes = tuple(axes)
        self.packed = packed
        self.real_dtype = grid.dtype if field_dtype is None else jnp.dtype(field_dtype)
        a1, a2 = self.axes
        p1, p2 = mesh_axes_size(mesh, a1), mesh_axes_size(mesh, a2)
        self.pencil = (p1, p2)
        self.chunk = resolve_chunk(chunk, grid.shape, p1, p2)

        f32 = np.float32
        k1, k2, k3 = grid.k_grids(rfft_last=False)
        d1, d2, d3 = grid.k_deriv(rfft_last=False)
        self.k = (k1.astype(f32), k2.astype(f32), k3.astype(f32))
        self.kd = (d1.astype(f32), d2.astype(f32), d3.astype(f32))
        self.ksq = (k1**2 + k2**2 + k3**2).astype(f32)
        self.ksq_d = (d1**2 + d2**2 + d3**2).astype(f32)

        spec_r = P(None, a1, a2, None)  # real-space pencils
        spec_k = P(None, None, a1, a2)  # k-space pencils
        kw = dict(a1=a1, a2=a2, p1=p1, p2=p2, chunk=self.chunk)
        self._fwd4 = shard_map(
            partial(_pipelined, _fwd_one, **kw), mesh=mesh,
            in_specs=(spec_r,), out_specs=spec_k, check_vma=False,
        )
        self._inv4 = shard_map(
            partial(_pipelined, _inv_one, **kw), mesh=mesh,
            in_specs=(spec_k,), out_specs=spec_r, check_vma=False,
        )

    # -- batching: leading dims are flattened into one batch axis so a single
    # rank-4 shard_map program serves scalars, vectors, and time series -----
    def _batched(self, fn, u):
        lead = u.shape[:-3]
        out = fn(u.reshape((-1,) + u.shape[-3:]))
        return out.reshape(lead + out.shape[-3:])

    @staticmethod
    def _wide(u: jnp.ndarray) -> jnp.ndarray:
        """Upcast sub-f32 real fields before the complex transform."""
        if u.dtype in (jnp.bfloat16, jnp.float16):
            return u.astype(jnp.float32)
        return u

    def fwd(self, u: jnp.ndarray) -> jnp.ndarray:
        with telemetry.annotate("pencil_fft.fwd"):
            return self._batched(self._fwd4, self._wide(u))

    def inv(self, spec: jnp.ndarray) -> jnp.ndarray:
        with telemetry.annotate("pencil_fft.inv"):
            return self._batched(self._inv4, spec).real.astype(self.real_dtype)

    def constrain_k(self, spec: jnp.ndarray) -> jnp.ndarray:
        """Pin a k-space array to this backend's pencil sharding.

        An explicit hint for jnp-level spectrum surgery between transforms
        (the multilevel zero-pad scatter): without it GSPMD's propagation
        pass may replicate the operand — on the folded multi-pod
        ``(pod, data)`` axis it all-gathered the whole coarse spectrum per
        chip (EXPERIMENTS §Dry-run).  No-op on layouts where propagation
        already keeps the array sharded.
        """
        a1, a2 = self.axes
        names = (None,) * (spec.ndim - 3) + (None, a1, a2)
        return jax.lax.with_sharding_constraint(
            spec, NamedSharding(self.mesh, P(*names))
        )

    def _reverse_k(self, spec: jnp.ndarray) -> jnp.ndarray:
        """``Z(k) -> Z((N - k) mod N)`` per space axis of a k-space array.

        ``(N - k) mod N`` is a full flip followed by a roll of 1.  Applied at
        the jnp level on the sharded spectrum: the flip/roll of the two
        sharded k axes lower to shard-reversing collective-permutes under
        GSPMD (no all-to-all re-pencilling).
        """
        ax = (-3, -2, -1)
        return jnp.roll(jnp.flip(spec, axis=ax), shift=(1, 1, 1), axis=ax)

    def fwd_packed(self, u: jnp.ndarray) -> jnp.ndarray:
        """Forward transform of ``(B, N1, N2, N3)`` REAL fields, two per ride.

        Pairs ``(u_{2i}, u_{2i+1})`` into ``u_{2i} + i u_{2i+1}``, transforms
        ``ceil(B/2)`` complex fields in ONE shard_map ride (an odd trailing
        field joins the same ride unpaired), and unpacks the two Hermitian
        spectra — halving the forward-side transpose traffic (the mirror of
        ``inv_packed``).
        """
        u = self._wide(u)
        b = u.shape[0]
        h = b // 2
        if h == 0:
            return self.fwd(u)
        with telemetry.annotate("pencil_fft.fwd_packed"):
            pairs = u[0 : 2 * h : 2] + 1j * u[1 : 2 * h : 2]  # (h, space)
            if b % 2:
                pairs = jnp.concatenate([pairs, u[2 * h :].astype(pairs.dtype)], axis=0)
            z = self._fwd4(pairs)
            zr = jnp.conj(self._reverse_k(z[:h]))  # conj Z(-k)
            fa = 0.5 * (z[:h] + zr)
            fb = -0.5j * (z[:h] - zr)
            out = jnp.stack([fa, fb], axis=1).reshape((2 * h,) + z.shape[1:])
            if b % 2:
                out = jnp.concatenate([out, z[h:]], axis=0)
            return out

    def inv_packed(self, spec: jnp.ndarray) -> jnp.ndarray:
        """Inverse of ``(B, N1, N2, N3)`` real-destined spectra, two per ride.

        Pairs ``(F_{2i}, F_{2i+1})`` into ``F_{2i} + i F_{2i+1}``, inverts
        ``ceil(B/2)`` spectra, and unpacks real/imag parts — halving the
        inverse-side transpose traffic (EXPERIMENTS §Perf).
        """
        b = spec.shape[0]
        h = b // 2
        if h == 0:
            return self.inv(spec)
        with telemetry.annotate("pencil_fft.inv_packed"):
            pairs = spec[0 : 2 * h : 2] + 1j * spec[1 : 2 * h : 2]
            if b % 2:
                pairs = jnp.concatenate([pairs, spec[2 * h :]], axis=0)
            z = self._inv4(pairs)
            out = jnp.stack([z[:h].real, z[:h].imag], axis=1).reshape(
                (2 * h,) + z.shape[1:]
            )
            if b % 2:
                out = jnp.concatenate([out, z[h:].real], axis=0)
            return out.astype(self.real_dtype)
