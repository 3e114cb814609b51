"""The main path's Pallas kernels and solver programs compile for a TPU v5e.

Interpret mode accepts block shapes and VMEM footprints the chip's compiler
refuses, so these tests compile — without a chip — for a *described*
``v5e:2x2`` topology, at the sizes ``chip_smoke.py`` runs: the TPU compiler
is installed here even where no TPU is attached.  Nothing runs, so this
checks that each program is accepted and really contains a Mosaic kernel
(``tpu_custom_call``), not that it computes the right numbers (the CPU
tests and ``chip_smoke.py`` do that).

The topology is described inside a module fixture (never at import: only
one process may hold the TPU library, and every test worker imports every
test file), and all such compiles live in this one file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import Solver, heterogeneous_jacobi, laplace_jacobi
from repro.kernels import jacobi2d_fused_step, stencil2d, stencil3d

V5E = "TPU v5 lite"

# chip_smoke.py's sizes: the Table-1 tile batch, the HBM-streamed grid, the
# Fig-6 tile batch and the four-chip halo grid.
TABLE1 = (131072, 64, 64)
GRID = (1, 8192, 8192)
FIG6 = (8192, 10, 64, 64)
HALO = (1, 16384, 16384)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("shape", [TABLE1, GRID], ids=["table1", "grid"])
@pytest.mark.parametrize("bc", [1.0, None], ids=["dirichlet", "raw"])
def test_stencil2d(shape, bc, one_chip, no_compile_cache):
    spec = laplace_jacobi(2)
    _compile(lambda x: stencil2d(x, spec, bc_value=bc, interpret=False),
             shape, sharding=one_chip)


@pytest.mark.parametrize("shape", [TABLE1, GRID], ids=["table1", "grid"])
@pytest.mark.parametrize("fuse", [4, 16])
def test_fused_trapezoid(shape, fuse, one_chip, no_compile_cache):
    spec = laplace_jacobi(2)
    _compile(lambda x: jacobi2d_fused_step(x, spec, fuse=fuse, bc_value=1.0,
                                           interpret=False),
             shape, sharding=one_chip)


@pytest.mark.parametrize("shape", [(1024, 64, 64), (1, 768, 768)],
                         ids=["tiles", "largest-resident"])
def test_fused_resident(shape, one_chip, no_compile_cache):
    # (1, 768, 768) is the largest square grid the v5e's VMEM budget admits
    # resident; its compile checks the budget (core/plan.py DEVICE_PROFILES,
    # kernels/tiling.py fits_vmem).
    from repro.core.plan import _pallas_fits, device_profile
    spec = laplace_jacobi(2)
    v5e = device_profile(V5E)
    assert _pallas_fits(spec, shape[1:], 16, v5e, rim="resident")
    assert not _pallas_fits(spec, (896, 896), 16, v5e, rim="resident")
    _compile(lambda x: jacobi2d_fused_step(x, spec, fuse=16, bc_value=1.0,
                                           rim="resident", interpret=False),
             shape, sharding=one_chip)


@pytest.mark.parametrize("fuse", [1, 4])
def test_widest_admitted_rows(fuse, one_chip, no_compile_cache):
    # The widest rows the VMEM budget admits at this depth still compile;
    # rows four times as wide are refused before they reach the compiler.
    from repro.core.plan import device_profile
    from repro.kernels.tiling import fits_vmem
    spec = laplace_jacobi(2)
    budget = device_profile(V5E).scoped_vmem_bytes
    assert fits_vmem((256, 16384), fuse, 1, budget=budget)
    assert not fits_vmem((256, 65536), fuse, 1, budget=budget)
    _compile(lambda x: jacobi2d_fused_step(x, spec, fuse=fuse, bc_value=1.0,
                                           interpret=False),
             (1, 256, 16384), sharding=one_chip)


def test_fused_variable_coefficients(one_chip, no_compile_cache):
    kappa = 1.0 + np.random.default_rng(0).random((1024, 1024), np.float32)
    spec = heterogeneous_jacobi(kappa)
    _compile(lambda x: jacobi2d_fused_step(x, spec, fuse=4, bc_value=1.0,
                                           interpret=False),
             (1, 1024, 1024), sharding=one_chip)


@pytest.mark.parametrize("shape", [FIG6, (1, 64, 64, 64)],
                         ids=["fig6", "cube"])
def test_stencil3d(shape, one_chip, no_compile_cache):
    spec = laplace_jacobi(3)
    _compile(lambda x: stencil3d(x, spec, bc_value=1.0, interpret=False),
             shape, sharding=one_chip)


def test_table1_solver_chunk(one_chip, no_compile_cache):
    # The fixed-sweep program chip_smoke.py runs first: backend="auto" on
    # the v5e profile picks a compiled Pallas plan.
    solver = Solver(laplace_jacobi(2), TABLE1[1:], bc=1.0, rtol=None,
                    atol=None, max_iters=500, device_kind=V5E,
                    interpret=False)
    assert solver.backend in ("pallas", "pallas_fused")
    assert not solver.plan.interpreted
    x = jax.ShapeDtypeStruct(TABLE1, jnp.float32, sharding=one_chip)
    compiled = solver.plan._fn.lower(x, None, None, None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < 16e9


def test_table1_converging_loop(one_chip, no_compile_cache):
    solver = Solver(laplace_jacobi(2), TABLE1[1:], bc=1.0, rtol=1e-5,
                    max_iters=20_000, device_kind=V5E, interpret=False)
    x = jax.ShapeDtypeStruct((1024, *TABLE1[1:]), jnp.float32,
                             sharding=one_chip)
    compiled = solver._loop.lower(x, None, None, None).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_halo_on_four_chips(topo, no_compile_cache):
    # The mesh jax.make_mesh returns (Explicit axes) must be accepted, and
    # the program must hold no grid-sized constant.
    mesh = jax.make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    solver = Solver(laplace_jacobi(2), HALO[1:], backend="halo", mesh=mesh,
                    bc=1.0, rtol=None, atol=None, max_iters=200,
                    device_kind=V5E)
    assert solver.fuse > 1
    x = jax.ShapeDtypeStruct(
        HALO, jnp.float32,
        sharding=NamedSharding(mesh, P(None, "data", "model")))
    compiled = solver.plan._fn.lower(x, None, None, None).compile()
    text = compiled.as_text()
    assert re.search(r" collective-permute(-start)?\(", text)
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes == HALO[1] * HALO[2] * 4 // 4
