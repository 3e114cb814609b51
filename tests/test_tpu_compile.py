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
# the table1-grid-fixed benchmark cell: one chip's 16384^2 grid
GRID_CELL = (1, 16384, 16384)


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


def test_sixteen_bit_rows(one_chip, no_compile_cache):
    # A bfloat16 grid is computed in float32 in VMEM, so its row blocks are
    # sized as a float32 grid's: rows of 16384 get 16-row blocks, which
    # compile (32-row ones need 16.43 MiB of scoped VMEM and did not), and
    # rows of 32768 (20.88 MiB) are refused before they reach the compiler.
    from repro.core.plan import device_profile
    from repro.kernels.tiling import fits_vmem, fused_block_geometry
    spec = laplace_jacobi(2)
    budget = device_profile(V5E).scoped_vmem_bytes
    assert fused_block_geometry(256, 16384, 4, 1, itemsize=2) == (16, 4)
    assert fits_vmem((256, 16384), 4, 1, budget=budget, itemsize=2)
    assert not fits_vmem((256, 32768), 4, 1, budget=budget, itemsize=2)
    args = [jax.ShapeDtypeStruct((1, 256, 16384), jnp.bfloat16,
                                 sharding=one_chip)]
    compiled = jax.jit(lambda x: jacobi2d_fused_step(
        x, spec, fuse=4, bc_value=1.0, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


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
    assert solver.backend == "pallas"
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


def _grid_copy(shape):
    """A ``copy`` of a grid shaped ``shape`` or stored batch-minor, the
    batch axis last, as the batch-minor kernel takes it."""
    dims = "|".join(re.escape(",".join(map(str, s)))
                    for s in (shape, (*shape[1:], shape[0])))
    return re.compile(rf"f32\[(?:{dims})\]\{{[^}}]*\}} copy\(")


def _while_bodies(text):
    """The text of each computation of ``text`` that a ``while`` runs."""
    comps = dict(re.findall(r"^(?:ENTRY )?%([\w.-]+) [^\n]*\{\n(.*?)\n\}$",
                            text, re.M | re.S))
    return [comps[b] for b in re.findall(r" while\(.*body=%([\w.-]+)", text)]


@pytest.mark.parametrize("ndim, shape, iters, calls", [
    (2, TABLE1, 100, 2), (3, FIG6, 100, 2), (2, (1024, 64, 64), None, 1),
], ids=["table1", "fig6", "table1-converge"])
def test_sweep_loop_copies_no_grid(ndim, shape, iters, calls, one_chip,
                                   no_compile_cache):
    # A Pallas call cannot write the buffer it reads, so a loop whose body
    # is one such call makes XLA copy the grid out of the loop's carry
    # before every call.  The fixed-sweep scans (25 fuse-4 passes, 100
    # sweeps) pair their calls and need no copy; the converging loop runs
    # one fuse-16 call a body, whose output the boundary pin rewrites.
    solver = Solver(laplace_jacobi(ndim), shape[1:], bc=1.0,
                    rtol=None if iters else 1e-5, atol=None,
                    max_iters=iters or 20_000, device_kind=V5E,
                    interpret=False)
    program = solver.plan._fn if iters else solver._loop
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = program.lower(x, None, None, None).compile()
    bodies = _while_bodies(compiled.as_text())
    grid_copy = _grid_copy(shape)
    assert bodies
    for body in bodies:
        assert not grid_copy.search(body)
        assert body.count('custom_call_target="tpu_custom_call"') == calls
    # the loop's two buffers, each lane-padded to 128, as before the pairing
    padded = np.prod(shape[:-1]) * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * padded + 2**20


def test_grid_sweep_loop(one_chip, no_compile_cache):
    # The table1-grid-fixed cell's program: 25 fuse-4 passes over one
    # 16384^2 grid in 16-row trapezoid blocks.  The pairs of calls need no
    # copy of the grid, and the only temporary beside the argument and the
    # output is the loop's second buffer (16384 lanes need no padding).
    solver = Solver(laplace_jacobi(2), GRID_CELL[1:], bc=1.0, rtol=None,
                    atol=None, max_iters=100, device_kind=V5E,
                    interpret=False)
    plan = solver.plan
    assert (plan.backend, plan.fuse, plan.block_rows, plan.halo_rows) == (
        "pallas", 4, 16, 4)
    assert plan.lane_axis(GRID_CELL[0]) == "cols"
    x = jax.ShapeDtypeStruct(GRID_CELL, jnp.float32, sharding=one_chip)
    compiled = plan._fn.lower(x, None, None, None).compile()
    assert "%jacobi2d_fused_step" in compiled.as_text()
    assert "jacobi2d_lanes_step" not in compiled.as_text()
    bodies = _while_bodies(compiled.as_text())
    grid_copy = re.compile(r"f32\[1,16384,16384\]\{[^}]*\} copy\(")
    assert bodies
    for body in bodies:
        assert not grid_copy.search(body)
        assert body.count('custom_call_target="tpu_custom_call"') == 2
    grid_bytes = np.prod(GRID_CELL) * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        grid_bytes + 2**20


@pytest.mark.parametrize("shape, fixed", [
    (TABLE1, True), ((1024, 64, 64), False), ((4096, 64, 64), False),
], ids=["table1", "converge-1024", "converge-4096"])
def test_tile_batch_on_the_lanes(shape, fixed, one_chip, no_compile_cache):
    # The 64x64 tile batches run the batch-minor kernel: XLA already stores
    # the (batch, 64, 64) argument batch-minor, so the transposes around the
    # kernel scan are bit casts and no copy of the grid is left, in either
    # order; Table 1's only temporary is the loop's second compact buffer.
    # The pin fused with the entry transpose keeps its ``repro.boundary``
    # scope, which ``boundary_share`` reads from a device trace.
    solver = Solver(laplace_jacobi(2), shape[1:], bc=1.0,
                    rtol=None if fixed else 1e-5, atol=None,
                    max_iters=100 if fixed else 20_000, device_kind=V5E,
                    interpret=False)
    assert solver.plan.lane_axis(shape[0]) == "batch"
    program = solver.plan._fn if fixed else solver._loop
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = program.lower(x, None, None, None).compile()
    text = compiled.as_text()
    assert "%jacobi2d_lanes_step" in text
    assert "jacobi2d_fused_step" not in text
    assert not _grid_copy(shape).search(text)
    # the pin, fused with the entry transpose, is still read as the pin
    pins = re.findall(r"= f32\[64,64,%d\]\S* fusion\(.*" % shape[0], text)
    assert pins and all("repro.boundary" in p for p in pins)
    if fixed:
        assert compiled.memory_analysis().temp_size_in_bytes <= \
            np.prod(shape) * 4 + 2**20


def test_widest_admitted_lane_blocks(one_chip, no_compile_cache):
    # The VMEM model of the batch-minor kernel (kernels/tiling.py
    # lanes_temp_blocks) admits 64x64 tiles at radius 1 and 48x64 at radius
    # 2, which compile; 80x64 at radius 1 and 64x64 at radius 2 need more
    # than the 16 MiB and keep their rows on the lanes.
    from repro.core import star
    from repro.core.plan import device_profile
    from repro.kernels import jacobi2d_lanes_step
    from repro.kernels.tiling import lane_axis
    budget = device_profile(V5E).scoped_vmem_bytes
    r2 = star(2, [0.1, 0.05], center=0.4)
    assert lane_axis((4096, 80, 64), laplace_jacobi(2), 1.0,
                     budget=budget) == "cols"
    assert lane_axis((4096, 64, 64), r2, 1.0, budget=budget) == "cols"
    for spec, tile in ((laplace_jacobi(2), (64, 64)), (r2, (48, 64))):
        assert lane_axis((4096, *tile), spec, 1.0, budget=budget) == "batch"
        _compile(lambda x: jacobi2d_lanes_step(x, spec, fuse=4, bc_value=1.0,
                                               interpret=False),
                 (*tile, 4096), sharding=one_chip)


def test_sixteen_bit_tiles(one_chip, no_compile_cache):
    # The bfloat16 control of the Table 1 cell takes the same kernel.
    solver = Solver(laplace_jacobi(2), TABLE1[1:], bc=1.0, rtol=None,
                    atol=None, max_iters=100, dtype=jnp.bfloat16,
                    device_kind=V5E, interpret=False)
    assert solver.plan.lane_axis(TABLE1[0]) == "batch"
    x = jax.ShapeDtypeStruct(TABLE1, jnp.bfloat16, sharding=one_chip)
    compiled = solver.plan._fn.lower(x, None, None, None).compile()
    assert "%jacobi2d_lanes_step" in compiled.as_text()


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
