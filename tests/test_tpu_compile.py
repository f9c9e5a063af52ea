"""Compile the main path for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode and the CPU backend accept:
misaligned Pallas blocks, kernels over the VMEM budget, programs over HBM,
sharding the partitioner cannot lower.  Each test here compiles one piece
of the registration main path at its real size for a ``v5e:2x2`` topology
described by ``jax.experimental.topologies``; nothing runs.

The topology is described inside a module fixture (never at import): only
one process may hold the TPU library, and the test workers all import this
file.  Code that asks ``jax.default_backend()`` is steered to its TPU branch
with ``monkeypatch``.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import gauss_newton as gn
from repro.core import objective as obj
from repro.core.grid import make_grid
from repro.core.spectral import SpectralOps
from repro.kernels import ops as kops
from repro.kernels.tricubic import (
    tricubic_apply_pallas_padded,
    tricubic_displace_pallas_padded_many,
)

HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch as on the chip: "auto" and "pallas" resolve to the native
    kernel with a lane-aligned tile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# (block shape, halo): the 128^3 grid on one chip, and one shard of 256^3 on
# the 2x2 pencil mesh — the ghost budgets the chip smoke runs them with
KERNEL_CASES = [((128, 128, 128), 8), ((128, 128, 256), 16)]


@pytest.mark.parametrize("shape,halo", KERNEL_CASES)
@pytest.mark.parametrize("c", [1, 2, 3])
@pytest.mark.parametrize("kind", ["planned", "displace"])
def test_pallas_kernel_compiles(one_chip, on_tpu, shape, halo, c, kind):
    rs = kops.resolve("pallas", shape)
    assert rs.method == "pallas" and not rs.interpret and rs.tile == (8, 8, 128)

    def sds(s, dt=jnp.float32):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    fpad = sds((c,) + tuple(n + 2 * halo + 3 for n in shape))
    if kind == "planned":
        fn = functools.partial(tricubic_apply_pallas_padded, tile=rs.tile, halo=halo)
        compiled = _compile(fn, fpad, sds((3,) + shape, jnp.int32), sds((3, 4) + shape))
    else:
        fn = functools.partial(tricubic_displace_pallas_padded_many, tile=rs.tile, halo=halo)
        compiled = _compile(fn, fpad, sds((3,) + shape))
    assert "tpu_custom_call" in compiled.as_text()


def _newton_args(grid, vec, scalar, replicated):
    """Iterate, forcing reference, images — as ``gn.solve`` calls."""
    v = jax.ShapeDtypeStruct((3,) + grid.shape, jnp.float32, sharding=vec)
    img = jax.ShapeDtypeStruct(grid.shape, jnp.float32, sharding=scalar)
    g0 = jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)
    return gn.Iterate(v, v), g0, img, img


def _newton_step(grid, ops, interp):
    cfg = gn.GNConfig(autotune="off")

    def step(v, g0, rho_R, rho_T):
        prob = obj.Problem(grid, rho_R, rho_T, cfg.beta, cfg.n_t, False)
        return gn.newton_iteration(v, g0, prob, ops, cfg, interp=interp)

    return step


def test_newton_iteration_128_fits_one_chip(one_chip, on_tpu):
    grid = make_grid((128,) * 3)
    step = _newton_step(grid, SpectralOps(grid), None)  # the default: Pallas on TPU
    compiled = _compile(step, *_newton_args(grid, one_chip, one_chip, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < HBM_BYTES, mem


def test_newton_step_on_2x2_mesh_compiles(topo, on_tpu):
    from repro.dist.context import DistContext
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    grid = make_grid((32, 32, 128))
    ctx = DistContext(grid, mesh, halo=4, autotune="off")
    assert ctx.interp.kernel.method == "pallas"  # the per-shard kernel is in it
    step = _newton_step(grid, ctx.ops, ctx.interp)
    args = _newton_args(grid, ctx.vector_sharding(), ctx.scalar_sharding(),
                        NamedSharding(mesh, P()))
    compiled = _compile(step, *args)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-to-all" in hlo


def test_cohort_step_scopes_reach_the_chip_program(one_chip, on_tpu):
    """The named scopes ride the chip program's ops as frontend attributes
    (what a TPU trace prints in an op's name), and the kernels keep their
    instruction names under them."""
    grid = make_grid((16, 16, 128))
    cfg = gn.GNConfig(autotune="off")
    step = gn.make_cohort_step(grid, cfg)

    def sds(s, dt=jnp.float32):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    v = sds((2, 3) + grid.shape)
    img = sds((2,) + grid.shape)
    hlo = step.lower(gn.Iterate(v, v), sds((2,)), sds((2,), jnp.bool_), sds(()), img,
                     img).compile().as_text()
    for name in ("gn.gradient", "gn.pcg", "gn.line_search", "gn.update"):
        assert f'gn="{name}"' in hlo, name
    kernels = [line for line in hlo.splitlines()
               if line.lstrip().startswith(("%tricubic_apply_pallas_padded",
                                            "%tricubic_displace_pallas_padded_many"))]
    assert {k.split(".")[0].strip() for k in kernels} == {
        "%tricubic_apply_pallas_padded", "%tricubic_displace_pallas_padded_many"}
    for k in kernels:
        scope = "interp.apply" if "apply" in k.split("=")[0] else "interp.displace"
        assert f'interp="{scope}"' in k and 'gn="gn.' in k, k
