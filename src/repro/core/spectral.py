"""Spectral (Fourier) differential operators on the periodic grid.

Everything the paper applies in Fourier space (§III-B1): gradients,
divergence, Laplacian, biharmonic ``Lap^2`` (regularization), their inverses
(preconditioner ``(beta Lap^2)^{-1}``), the Leray projection
``P = I - grad Lap^{-1} div`` that eliminates the incompressibility
constraint, and the Gaussian smoothing applied to input images.

All operators are diagonal scalings of the FFT coefficients, so each costs a
forward transform, an O(N^3) scaling, and an inverse transform.  The
``FFTBackend`` abstraction lets the same operator definitions run on a single
device (``LocalFFT``: rfft) or on the production mesh
(``repro.dist.pencil_fft.PencilFFT``: the paper's pencil-decomposed parallel
FFT expressed with ``shard_map`` + ``lax.all_to_all``; wired up by
``repro.dist.context.DistContext`` as ``ctx.ops``).  The backends may use
different spectrum layouts (rfft vs full c2c) — operators only ever pair a
backend's ``fwd``/``inv`` with that same backend's ``k``/``kd``/``ksq``
grids, so the difference never leaks.

**Transform coalescing** (``SpectralOps.batch()`` / ``SpectralBatch``): on
the mesh every forward/inverse ride is a latency-bound pair of all-to-all
transposes, and one Newton iteration used to issue dozens of them — one
pair per operator call, strictly serialized.  Independent operator calls
are diagonal in k-space, so they compose into ONE big-batch forward over
the (deduplicated) stacked inputs and ONE big-batch inverse over the
stacked outputs:

    with ops.batch() as sb:
        divv = sb.div(v)          # handles resolve after the ride
        regv = sb.reg_apply(v, beta)
        lapv = sb.laplacian(v)
    g = regv.get() + ...          # all three shared ONE fwd + ONE inv

Inputs are deduplicated by identity (``div v``, ``reg v``, ``lap v`` above
transform ``v`` once), and both rides go through the backend's packed
transforms when available — the FFT-side mirror of the plan-once/apply-many
interpolation batching (EXPERIMENTS §Perf).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.grid import Grid


# --------------------------------------------------------------------------- #
# spectral truncation helpers (repro.multilevel transfer operators)
#
# Coarsening a periodic spectral discretization is exact mode selection: the
# coarse grid of size M carries the modes k in {0..ceil(M/2)-1, -M//2..-1}.
# ``mode_indices`` maps those modes to their positions in a length-N fine
# spectrum (numpy fft ordering), ``nyquist_mask`` zeroes the +-M/2 plane —
# the coarse Nyquist mode has no consistent counterpart on the fine grid
# (it aliases +M/2 and -M/2), so both restriction and prolongation drop it;
# that symmetric convention keeps the pair exactly adjoint under the grids'
# cell-volume-weighted inner products.
# --------------------------------------------------------------------------- #
def mode_indices(n_fine: int, n_coarse: int, rfft: bool = False) -> np.ndarray:
    """Positions of the coarse grid's modes inside a length-``n_fine`` spectrum.

    Returned in coarse-spectrum order, so ``fine_spec[idx]`` IS the coarse
    spectrum (up to normalization) and ``fine_spec[idx] = coarse_spec``
    zero-pads.  ``rfft=True`` addresses an rfft last axis (modes 0..n/2).
    The index set is two contiguous runs (head of positive modes, tail of
    negative modes) — ``repro.multilevel.transfer`` exploits that to express
    truncation/zero-padding as slices+concat instead of gather/scatter.
    """
    if n_coarse > n_fine:
        raise ValueError(f"coarse axis {n_coarse} exceeds fine axis {n_fine}")
    if rfft:
        return np.arange(n_coarse // 2 + 1)
    n_pos = n_coarse - n_coarse // 2  # modes 0 .. ceil(M/2)-1
    n_neg = n_coarse // 2  # modes -M//2 .. -1
    return np.concatenate([np.arange(n_pos), np.arange(n_fine - n_neg, n_fine)])


def nyquist_mask(n_fine: int, n_coarse: int, rfft: bool = False) -> np.ndarray:
    """1.0 per retained mode, 0.0 on the coarse Nyquist plane (even M < N)."""
    size = n_coarse // 2 + 1 if rfft else n_coarse
    mask = np.ones(size, np.float32)
    if n_coarse % 2 == 0 and n_coarse < n_fine:
        mask[n_coarse // 2] = 0.0
    return mask


class LocalFFT:
    """Single-device backend: real FFT over the last three axes."""

    def __init__(self, grid: Grid):
        self.grid = grid
        k1, k2, k3 = grid.k_grids(rfft_last=True)
        d1, d2, d3 = grid.k_deriv(rfft_last=True)
        f32 = np.float32
        self.k = (k1.astype(f32), k2.astype(f32), k3.astype(f32))
        self.kd = (d1.astype(f32), d2.astype(f32), d3.astype(f32))
        self.ksq = (k1**2 + k2**2 + k3**2).astype(f32)
        self.ksq_d = (d1**2 + d2**2 + d3**2).astype(f32)
        # Parseval weight of each stored rfft mode: the half-spectrum drops
        # the conjugate partner of every 0 < k3 < N3/2 mode, so those count
        # twice in sum_k |U(k)|^2; k3 = 0 and the (even-N3) Nyquist plane
        # are self-conjugate and count once.
        n3 = grid.shape[2]
        w = np.full(n3 // 2 + 1, 2.0, f32)
        w[0] = 1.0
        if n3 % 2 == 0:
            w[-1] = 1.0
        self.spec_weight = w.reshape(1, 1, -1)

    def fwd(self, u: jnp.ndarray) -> jnp.ndarray:
        if u.dtype not in (jnp.float32, jnp.float64):
            u = u.astype(jnp.float32)  # rfft rejects bf16/f16 payloads
        return jnp.fft.rfftn(u, axes=(-3, -2, -1))

    def inv(self, spec: jnp.ndarray) -> jnp.ndarray:
        n = self.grid.shape
        return jnp.fft.irfftn(spec, s=n, axes=(-3, -2, -1)).astype(self.grid.dtype)


class SpectralRef:
    """Lazy handle for one coalesced op's output (see ``SpectralBatch``)."""

    __slots__ = ("_batch", "_idx")

    def __init__(self, batch: "SpectralBatch", idx: int):
        self._batch = batch
        self._idx = idx

    def get(self) -> jnp.ndarray:
        """Resolve the result (runs the batch's single ride pair if needed)."""
        self._batch.run()
        return self._batch._results[self._idx]


class SpectralBatch:
    """Coalesce independent spectral operator calls into ONE forward and ONE
    inverse transform ride.

    Each enqueued op records (input fields, a k-space transfer function,
    output layout); ``run()`` — triggered by the context-manager exit or the
    first ``SpectralRef.get()`` — concatenates the deduplicated inputs,
    performs one batched real forward (packed on ``PencilFFT``), applies
    every op's diagonal k-space math, and inverts the stacked real-destined
    outputs in one batched ride.  On a pencil mesh this turns K serialized
    all-to-all pairs into 1 per direction; locally it amortizes rfft plan
    overhead across the stack.  Results are exactly the packed-transform
    composition of the eager operators (parity pinned in
    ``tests/test_spectral.py`` / the mesh legs of ``tests/test_coalesce.py``).
    """

    def __init__(self, ops: "SpectralOps"):
        self.ops = ops
        self._in_arrays: list = []  # flat (m, N1, N2, N3) blocks
        self._in_slots: dict = {}  # id(array) -> (start, array)
        self._n_in = 0
        self._jobs: list = []  # (in_slices, kfn, out_lead)
        self._results: list | None = None

    # -- plumbing ----------------------------------------------------------
    def _input(self, u: jnp.ndarray):
        """Register a real input field; dedup by identity. Returns (start, lead)."""
        if self._results is not None:
            raise RuntimeError("SpectralBatch already ran; start a new batch")
        space = u.shape[-3:]
        if space != tuple(self.ops.grid.shape):
            raise ValueError(f"field shape {u.shape} not on grid {self.ops.grid.shape}")
        lead = u.shape[:-3]
        slot = self._in_slots.get(id(u))
        if slot is not None and slot[1] is u:
            return slot[0], lead
        m = int(np.prod(lead)) if lead else 1
        start = self._n_in
        self._in_arrays.append(u.reshape((m,) + space))
        self._n_in += m
        self._in_slots[id(u)] = (start, u)
        return start, lead

    def _job(self, inputs, kfn, out_lead, reduce: bool = False) -> SpectralRef:
        """Enqueue one op: ``kfn(*specs) -> out_lead + kshape`` spectrum.

        ``reduce=True`` marks a *spectrum-side reduction*: ``kfn`` returns
        the job's final value directly (e.g. a Parseval norm) and the job
        contributes nothing to the inverse ride — a batch of only reduction
        jobs costs ONE forward and ZERO inverse transforms.
        """
        slots = [self._input(u) for u in inputs]
        self._jobs.append((slots, kfn, tuple(out_lead), reduce))
        return SpectralRef(self, len(self._jobs) - 1)

    def run(self) -> None:
        """Execute the coalesced ride pair (idempotent)."""
        if self._results is not None:
            return
        if not self._jobs:
            self._results = []
            return
        self._results = [None] * len(self._jobs)
        ins = (
            self._in_arrays[0]
            if len(self._in_arrays) == 1
            else jnp.concatenate(self._in_arrays, axis=0)
        )
        specs = self.ops.fwd_real(ins)  # (B_in,) + kshape, one packed ride
        kshape = specs.shape[1:]
        out_blocks, inv_slots = [], []
        for idx, (slots, kfn, out_lead, reduce) in enumerate(self._jobs):
            args = [
                specs[start : start + max(int(np.prod(lead)), 1)].reshape(lead + kshape)
                for start, lead in slots
            ]
            out = kfn(*args)
            if reduce:  # already real-valued; skips the inverse ride
                self._results[idx] = out
            else:
                out_blocks.append(out.reshape((-1,) + kshape))
                inv_slots.append((idx, out_lead))
        if out_blocks:
            allspec = (
                out_blocks[0]
                if len(out_blocks) == 1
                else jnp.concatenate(out_blocks, axis=0)
            )
            real = self.ops.inv_real(allspec)  # one packed ride
            pos = 0
            for idx, out_lead in inv_slots:
                m = int(np.prod(out_lead)) if out_lead else 1
                self._results[idx] = real[pos : pos + m].reshape(
                    out_lead + real.shape[1:]
                )
                pos += m
        # drop input/job references: in eager use a retained handle must not
        # pin the stacked input buffers (the results are already extracted)
        self._in_arrays.clear()
        self._in_slots.clear()
        self._jobs.clear()

    def __enter__(self) -> "SpectralBatch":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.run()

    # -- coalesced operators (same semantics as the eager SpectralOps) -----
    def grad(self, f: jnp.ndarray) -> SpectralRef:
        return self._job([f], self.ops._grad_spec, (3,) + f.shape[:-3])

    def div(self, v: jnp.ndarray) -> SpectralRef:
        return self._job([v], self.ops._div_spec, v.shape[:-4])

    def laplacian(self, f: jnp.ndarray) -> SpectralRef:
        return self._job([f], lambda s: -self.ops.fft.ksq * s, f.shape[:-3])

    def biharmonic(self, f: jnp.ndarray) -> SpectralRef:
        return self._job([f], lambda s: self.ops.fft.ksq**2 * s, f.shape[:-3])

    def inv_laplacian(self, f: jnp.ndarray) -> SpectralRef:
        return self._job([f], lambda s: self.ops._inv_lap_scale() * s, f.shape[:-3])

    def inv_biharmonic(self, f: jnp.ndarray, zero_mode: float = 0.0) -> SpectralRef:
        return self._job(
            [f], lambda s: self.ops._inv_bihar_scale(zero_mode) * s, f.shape[:-3]
        )

    def reg_apply(self, v: jnp.ndarray, beta) -> SpectralRef:
        return self._job([v], lambda s: self.ops._reg_scale(beta) * s, v.shape[:-3])

    def precond_apply(self, r: jnp.ndarray, beta) -> SpectralRef:
        return self._job([r], lambda s: self.ops._precond_scale(beta) * s, r.shape[:-3])

    def leray(self, v: jnp.ndarray) -> SpectralRef:
        return self._job([v], self.ops._leray_spec, v.shape[:-3])

    def precond_project(self, r: jnp.ndarray, beta, incompressible: bool) -> SpectralRef:
        def kfn(s):
            s = self.ops._precond_scale(beta) * s
            return self.ops._leray_spec(s) if incompressible else s

        return self._job([r], kfn, r.shape[:-3])

    def reg_plus_project(
        self, a: jnp.ndarray, b: jnp.ndarray, beta, incompressible: bool
    ) -> SpectralRef:
        """beta Lap^2 a + P b (P = I when not incompressible): the Newton
        gradient/Hessian assembly, 6 fields forward -> 3 back."""

        def kfn(sa, sb):
            if incompressible:
                sb = self.ops._leray_spec(sb)
            return self.ops._reg_scale(beta) * sa + sb

        return self._job([a, b], kfn, a.shape[:-3])

    def smooth(self, f: jnp.ndarray, sigma=None) -> SpectralRef:
        scale = self.ops._smooth_scale(sigma)
        return self._job([f], lambda s: scale * s, f.shape[:-3])

    def reg_energy(self, v: jnp.ndarray, beta) -> SpectralRef:
        """beta/2 ||Lap v||^2 as a spectrum-side Parseval reduction.

        Shares the batch's one forward ride with every other job on ``v``
        and joins NO inverse ride — the Armijo-trial lever: a line-search
        objective evaluation reads the energy straight off the forward
        spectrum instead of paying a dedicated forward/inverse pair
        (ride-count pinned by ``tests/test_coalesce.py``).
        """
        return self._job(
            [v],
            lambda s: self.ops._reg_energy_spec(s, beta),
            v.shape[:-4],
            reduce=True,
        )


class SpectralOps:
    """Paper's spectral operator toolbox over a pluggable FFT backend.

    ``field_dtype`` (e.g. ``jnp.bfloat16``) selects the storage dtype of
    every real-space field an operator RETURNS — the transport/FFT field
    path of the mixed-precision knob (`repro.autotune`).  The transforms
    and all k-space scalings stay complex64/f32 (inputs are upcast on the
    forward side), so only the stored fields lose precision; critical
    accumulations (inner products, time quadrature, the PCG recursion)
    remain >= f32 by construction elsewhere.
    """

    def __init__(self, grid: Grid, backend=None, field_dtype=None):
        self.grid = grid
        self.fft = backend if backend is not None else LocalFFT(grid)
        self.field_dtype = None if field_dtype is None else jnp.dtype(field_dtype)

    def batch(self) -> SpectralBatch:
        """Open a transform-coalescing batch (see ``SpectralBatch``)."""
        return SpectralBatch(self)

    @telemetry.annotate("fft.inv")
    def inv_real(self, spec: jnp.ndarray) -> jnp.ndarray:
        """Inverse transform of real-destined spectra; uses the backend's
        complex-packed inverse (PencilFFT(packed=True)) when available —
        halves inverse-side all-to-all bytes (EXPERIMENTS §Perf).  Every
        inverse of the toolbox passes here, under the named scope
        ``fft.inv``."""
        if getattr(self.fft, "packed", False) and spec.ndim > 3:
            lead = spec.shape[:-3]
            flat = spec.reshape((-1,) + spec.shape[-3:])
            out = self.fft.inv_packed(flat)
            out = out.reshape(lead + out.shape[-3:])
        else:
            out = self.fft.inv(spec)
        if self.field_dtype is not None:
            out = out.astype(self.field_dtype)
        return out

    @telemetry.annotate("fft.fwd")
    def fwd_real(self, u: jnp.ndarray) -> jnp.ndarray:
        """Forward transform of REAL fields; pairs of a batched stack ride
        the backend's packed forward (``PencilFFT.fwd_packed``) when
        available — the forward-side mirror of ``inv_real``, halving the
        forward all-to-all bytes of gradient/Leray/coalesced-batch stacks.
        Every forward of the toolbox passes here, under the named scope
        ``fft.fwd``."""
        if getattr(self.fft, "packed", False) and u.ndim > 3:
            lead = u.shape[:-3]
            flat = u.reshape((-1,) + u.shape[-3:])
            out = self.fft.fwd_packed(flat)
            return out.reshape(lead + out.shape[-3:])
        return self.fft.fwd(u)

    # backwards-compatible aliases (pre-coalescing internal names)
    _inv_real = inv_real
    _fwd_real = fwd_real

    # ------------------------------------------------------------------ #
    # k-space transfer functions, shared by the eager operators below and
    # the coalesced SpectralBatch ops above.  Underscored but package-
    # internal shared API: the multilevel layers compose with them too
    # (precond.py applies _leray_spec/_precond_scale as k-space multipliers
    # inside the V-cycle's spectrum-level split, transfer.smooth_restrict
    # rides _smooth_scale on its own forward) — change signatures here and
    # grep repro/multilevel along with this file.
    # ------------------------------------------------------------------ #
    def _grad_spec(self, spec: jnp.ndarray) -> jnp.ndarray:
        """(...,) spectrum -> (3, ...) gradient spectrum (Nyquist-zeroed)."""
        return jnp.stack([1j * k * spec for k in self.fft.kd], axis=0)

    def _div_spec(self, spec: jnp.ndarray) -> jnp.ndarray:
        """(..., 3, k-shape) spectrum -> (..., k-shape) divergence spectrum."""
        return sum(1j * k * spec[..., i, :, :, :] for i, k in enumerate(self.fft.kd))

    def _leray_spec(self, spec: jnp.ndarray) -> jnp.ndarray:
        """Apply P = I - k k^T/|k|^2 in k-space over the ``-4`` component
        axis of a (..., 3, k-shape) spectrum ((3, ...) single, (S, 3, ...)
        cohort — leading dims batch)."""
        kd = self.fft.kd
        ksq = self.fft.ksq_d
        comp = [spec[..., i, :, :, :] for i in range(3)]
        kdotv = sum(k * comp[i] for i, k in enumerate(kd))
        inv = jnp.where(ksq > 0, 1.0 / jnp.maximum(ksq, 1e-30), 0.0)
        return jnp.stack([comp[i] - kd[i] * inv * kdotv for i in range(3)], axis=-4)

    def _inv_lap_scale(self) -> jnp.ndarray:
        ksq = self.fft.ksq
        return jnp.where(ksq > 0, -1.0 / jnp.maximum(ksq, 1e-30), 0.0)

    def _inv_bihar_scale(self, zero_mode: float) -> jnp.ndarray:
        ksq = self.fft.ksq
        return jnp.where(ksq > 0, 1.0 / jnp.maximum(ksq**2, 1e-30), zero_mode)

    def _reg_scale(self, beta) -> jnp.ndarray:
        """Diagonal of A = beta Lap^2."""
        return beta * self.fft.ksq**2

    def _precond_scale(self, beta) -> jnp.ndarray:
        ksq = self.fft.ksq
        return jnp.where(ksq > 0, 1.0 / jnp.maximum(beta * ksq**2, 1e-30), 1.0)

    def _smooth_scale(self, sigma=None) -> jnp.ndarray:
        if sigma is None:
            sigma = self.grid.spacing
        if np.isscalar(sigma):
            sigma = (sigma, sigma, sigma)
        k1, k2, k3 = self.fft.k
        expo = -0.5 * ((k1 * sigma[0]) ** 2 + (k2 * sigma[1]) ** 2 + (k3 * sigma[2]) ** 2)
        return jnp.exp(expo)

    def _reg_energy_spec(self, spec: jnp.ndarray, beta) -> jnp.ndarray:
        """beta/2 ||Lap v||^2 read off the FORWARD spectrum of ``v`` (Parseval).

        For the unnormalized DFT, ``h^3 sum_x |u|^2 = h^3/N sum_k |U(k)|^2``;
        a half-spectrum backend (``LocalFFT``: rfft last axis) supplies
        ``spec_weight`` to double the modes whose conjugate partners it
        drops.  Equals the real-space quadrature of ``inv(-ksq * spec)`` to
        roundoff — without the inverse transform (the spectrum-side lever
        used by ``SpectralBatch.reg_energy``).  Reduces the component +
        space axes, so a cohort ``(S, 3, k..)`` spectrum yields ``(S,)``.
        """
        mag = spec.real**2 + spec.imag**2  # f32 accumulation from complex64
        w = getattr(self.fft, "spec_weight", None)
        if w is not None:
            mag = mag * w
        e = jnp.sum(self.fft.ksq**2 * mag, axis=(-4, -3, -2, -1))
        scale = self.grid.cell_volume / self.grid.num_points
        return 0.5 * beta * scale * e

    # ------------------------------------------------------------------ #
    # first-order operators (Nyquist-zeroed wavenumbers, skew-adjoint)
    # ------------------------------------------------------------------ #
    def grad(self, f: jnp.ndarray) -> jnp.ndarray:
        """grad f: (..., N1,N2,N3) -> (3, ..., N1,N2,N3).

        One forward FFT, three diagonal scalings, a *batched* inverse FFT —
        the paper's §III-C1 optimization to avoid three full 3-D round trips.
        """
        return self.inv_real(self._grad_spec(self.fwd_real(f)))

    def div(self, v: jnp.ndarray) -> jnp.ndarray:
        """div v: (..., 3, N1,N2,N3) -> (..., N1,N2,N3) (leading dims batch)."""
        spec = self.fwd_real(v)  # batched over the component axis
        return self.inv_real(self._div_spec(spec))

    # ------------------------------------------------------------------ #
    # even-order elliptic operators (full wavenumbers)
    # ------------------------------------------------------------------ #
    def laplacian(self, f: jnp.ndarray) -> jnp.ndarray:
        return self.inv_real(-self.fft.ksq * self.fwd_real(f))

    def biharmonic(self, f: jnp.ndarray) -> jnp.ndarray:
        return self.inv_real(self.fft.ksq**2 * self.fwd_real(f))

    def inv_laplacian(self, f: jnp.ndarray) -> jnp.ndarray:
        """Lap^{-1} with the zero mean mode mapped to zero."""
        return self.inv_real(self._inv_lap_scale() * self.fwd_real(f))

    def inv_biharmonic(self, f: jnp.ndarray, zero_mode: float = 0.0) -> jnp.ndarray:
        return self.inv_real(self._inv_bihar_scale(zero_mode) * self.fwd_real(f))

    # ------------------------------------------------------------------ #
    # Leray projection: P = I - grad Lap^{-1} div  (paper eq. (4))
    # ------------------------------------------------------------------ #
    def leray(self, v: jnp.ndarray) -> jnp.ndarray:
        """Project a velocity onto the divergence-free subspace.

        In Fourier space ``P_ij = delta_ij - k_i k_j / |k|^2``.  We use the
        Nyquist-zeroed ``k`` in both numerator and denominator so that
        ``P`` is an exact projection (P^2 = P) and ``div(P v) = 0`` exactly
        in the discrete spectral sense.  The k=0 (mean-velocity) mode is
        untouched: a constant field is divergence free.
        """
        return self.inv_real(self._leray_spec(self.fwd_real(v)))

    # ------------------------------------------------------------------ #
    # regularization operator A = beta Lap^2 and spectral preconditioner
    # ------------------------------------------------------------------ #
    def reg_apply(self, v: jnp.ndarray, beta) -> jnp.ndarray:
        """beta * Lap^2 v  (H^2 seminorm regularization, paper eq. (2a))."""
        return self.inv_real(self._reg_scale(beta) * self.fwd_real(v))

    def precond_apply(self, r: jnp.ndarray, beta) -> jnp.ndarray:
        """(beta Lap^2)^{-1} r — the paper's spectral preconditioner.

        Singular at k=0; the mean mode is passed through unchanged (there
        the Hessian is dominated by the data term, which is O(1)).
        """
        return self.inv_real(self._precond_scale(beta) * self.fwd_real(r))

    # ------------------------------------------------------------------ #
    # fused elliptic ops (beyond-paper; EXPERIMENTS §Perf)
    #
    # The paper applies A = beta Lap^2 and the Leray projection as separate
    # spectral round trips.  Both are diagonal (resp. 3x3-block diagonal)
    # in k-space, so one batched forward over [a, b], a k-space combine,
    # and ONE batched inverse computes  beta Lap^2 a + P b  — the
    # single-ride-pair form the coalesced Newton hot path uses
    # (core/objective.py); the fused preconditioner P (beta Lap^2)^{-1}
    # likewise halves its round trips.
    # ------------------------------------------------------------------ #
    def reg_plus_project(self, a: jnp.ndarray, b: jnp.ndarray, beta, incompressible: bool):
        """beta Lap^2 a + P b  (P = I when not incompressible) — one batched
        forward over the 6 stacked components, one batched inverse over 3."""
        spec = self.fwd_real(jnp.stack([a, b], axis=0))  # (2, 3, k...)
        sa, sb = spec[0], spec[1]
        if incompressible:
            sb = self._leray_spec(sb)
        return self.inv_real(self._reg_scale(beta) * sa + sb)

    def precond_project(self, r: jnp.ndarray, beta, incompressible: bool) -> jnp.ndarray:
        """P (beta Lap^2)^{-1} r in a single spectral round trip."""
        spec = self._precond_scale(beta) * self.fwd_real(r)
        if incompressible:
            spec = self._leray_spec(spec)
        return self.inv_real(spec)

    # ------------------------------------------------------------------ #
    # image preprocessing (paper §III-B1)
    # ------------------------------------------------------------------ #
    def smooth(self, f: jnp.ndarray, sigma=None) -> jnp.ndarray:
        """Gaussian spectral filter; default bandwidth = one grid cell."""
        return self.inv_real(self._smooth_scale(sigma) * self.fwd_real(f))

    # ------------------------------------------------------------------ #
    # diagnostics
    # ------------------------------------------------------------------ #
    def reg_energy(self, v: jnp.ndarray, beta) -> jnp.ndarray:
        """beta/2 ||Lap v||^2 via Parseval on the forward spectrum (mesh
        independent; equals the real-space quadrature of ``Lap v`` to
        roundoff, pinned by ``tests/test_spectral.py``) — one forward
        transform, NO inverse.

        A cohort velocity ``(S, 3, N..)`` returns per-subject energies
        ``(S,)`` (one batched transform for the whole cohort)."""
        return self._reg_energy_spec(self.fwd_real(v), beta)

    def jacobian_det(self, disp: jnp.ndarray) -> jnp.ndarray:
        """det(grad y) for y = x + u given displacement u (3,N1,N2,N3).

        grad u is computed spectrally; det(I + grad u) pointwise.
        """
        g = jnp.swapaxes(self.grad(disp), 0, 1)  # g[i,j] = d_j u_i, one batched FFT
        a = g + jnp.eye(3, dtype=g.dtype)[:, :, None, None, None]
        det = (
            a[0, 0] * (a[1, 1] * a[2, 2] - a[1, 2] * a[2, 1])
            - a[0, 1] * (a[1, 0] * a[2, 2] - a[1, 2] * a[2, 0])
            + a[0, 2] * (a[1, 0] * a[2, 1] - a[1, 1] * a[2, 0])
        )
        return det
