"""Pallas kernel sweeps vs pure-jnp oracles (deliverable c).

tricubic: shapes x dtypes x halos (also in test_interp.py);
spectral_diag: the fused biharmonic diagonal vs numpy k-grids.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import smooth_disp, sweep_widths

from repro.core.grid import make_grid
from repro.kernels import ref
from repro.kernels.spectral_diag import biharmonic_scale_pallas
from repro.kernels.tricubic import (
    LANE_CHUNK,
    ROW_CHUNK,
    tricubic_displace_pallas,
    tricubic_displace_pallas_many,
)


@pytest.mark.parametrize("n", [(8, 16, 128), (16, 8, 256)])
@pytest.mark.parametrize("betas", [(1.0,), (1e-2, 1.0)])
def test_spectral_diag_matches_kgrid(rng, n, betas):
    grid = make_grid(n)
    k1, k2, k3 = grid.k_grids(rfft_last=False)
    ksq = (k1**2 + k2**2 + k3**2).astype(np.float32)
    re = jnp.asarray(rng.standard_normal(n), jnp.float32)
    im = jnp.asarray(rng.standard_normal(n), jnp.float32)
    out_re, out_im = biharmonic_scale_pallas(re, im, betas=betas, tile=(8, 128), interpret=True)
    for c, beta in enumerate(betas):
        sym = beta * ksq**2
        np.testing.assert_allclose(out_re[c], np.asarray(re) * sym, rtol=2e-5)
        np.testing.assert_allclose(out_im[c], np.asarray(im) * sym, rtol=2e-5)


def test_spectral_diag_is_reg_apply(rng):
    """Kernel output ifft'd == SpectralOps.reg_apply (the paper's operator)."""
    from repro.core.spectral import SpectralOps

    n = (8, 16, 128)
    grid = make_grid(n)
    ops = SpectralOps(grid)
    f = jnp.asarray(rng.standard_normal(n), jnp.float32)
    spec = jnp.fft.fftn(f)
    out_re, out_im = biharmonic_scale_pallas(
        spec.real.astype(jnp.float32), spec.imag.astype(jnp.float32),
        betas=(1e-2,), tile=(8, 128), interpret=True,
    )
    got = jnp.fft.ifftn(out_re[0] + 1j * out_im[0]).real
    np.testing.assert_allclose(got, ops.reg_apply(f, 1e-2), atol=2e-2, rtol=1e-3)


# (halo, smooth amplitudes or None for uniform noise, clip at the halo, C):
# the solver's budgets 8 (128^3) and 16 (256^3 shard) on smooth fields whose
# row and lane sweeps are not whole chunks, and fields reaching the window's rim
HALO_SWEEP = [
    pytest.param(2, None, False, 1, id="2"),
    pytest.param(4, None, False, 1, id="4"),
    pytest.param(6, None, False, 1, id="6"),
    pytest.param(8, (2.0, 1.5, 3.3), False, 1, id="8-smooth-C1"),
    pytest.param(8, (1.0, 0.5, 1.7), False, 2, id="8-narrow-C2"),
    pytest.param(8, (10.4, 10.4, 10.4), True, 2, id="8-edge-C2"),
    pytest.param(16, (3.0, 2.0, 7.0), False, 2, id="16-smooth-C2"),
    pytest.param(16, (20.8, 20.8, 20.8), True, 1, id="16-edge-C1"),
]


@pytest.mark.parametrize("halo,amps,edge,c", HALO_SWEEP)
def test_tricubic_pallas_halo_sweep(rng, halo, amps, edge, c):
    if amps is None:
        shape, tile = (16, 16, 32), (8, 8, 16)
        d = rng.uniform(-halo + 0.05, halo - 0.05, (3,) + shape)
    else:
        # T3 is half the staged row, as on the chip, and the row does not
        # repeat with period T3: each lane half is read for what it holds
        shape, tile = (16, 8, 128), (8, 8, 64)
        d = smooth_disp(shape, amps, edge=halo if edge else None)
        assert np.any(sweep_widths(d, tile, 2) % LANE_CHUNK)
        assert np.any(sweep_widths(d, tile, 1) % ROW_CHUNK)
        assert (np.max(np.abs(d)) == halo) == edge
    f = rng.standard_normal((c,) + shape).astype(np.float32)
    d = d.astype(np.float32)
    out = tricubic_displace_pallas_many(jnp.asarray(f), jnp.asarray(d), tile=tile, halo=halo,
                                        interpret=True)
    # the oracle in float64: in f32 it forms x + d, and at N3 = 128 that
    # alone rounds the stencil weights by ~3e-5 of the field
    with jax.enable_x64(True):
        want = np.stack([ref.tricubic_displace(jnp.asarray(x, jnp.float64),
                                               jnp.asarray(d, jnp.float64)) for x in f])
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-4)


def test_tricubic_pallas_zero_disp_exact(rng):
    shape = (8, 8, 32)
    f = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    out = tricubic_displace_pallas(f, jnp.zeros((3,) + shape), tile=(4, 4, 16), halo=2, interpret=True)
    np.testing.assert_allclose(out, f, atol=1e-6)
