"""The batch-minor Jacobi kernel (``kernels/jacobi_fused.py::
jacobi2d_lanes_step``) and the rule that chooses it (``kernels/tiling.py::
lane_axis``, recorded on the plan as ``StencilPlan.lane_axis``).

The kernel sweeps a tile batch stored (H, W, batch), one tile on each lane;
it must compute what ``jacobi2d_fused_step`` computes on the same batch
stored (batch, H, W), and what the plain oracle ``core/reference.py``
computes, for batches that fill whole 128-lane blocks and for a ragged last
block.  Interpret mode on the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DirichletBC, heterogeneous_jacobi, laplace_jacobi,
                        make_plan, star)
from repro.core.reference import jacobi_reference
from repro.kernels import (jacobi2d, jacobi2d_fused_step, jacobi2d_lanes_step,
                           jacobi3d)
from repro.kernels.tiling import lane_axis, lane_block

V5E = "TPU v5 lite"
BUDGET = 16 << 20    # the v5e's scoped VMEM (core/plan.py DEVICE_PROFILES)
SPEC = laplace_jacobi(2)
BC = 1.0


def _batch(shape, dtype, seed=0):
    x = np.random.default_rng(seed).random(shape, np.float32)
    return jnp.asarray(x, dtype)


def _lanes(x, spec, fuse):
    """The batch-minor kernel on a (batch, H, W) array, back in that order."""
    y = jacobi2d_lanes_step(jnp.transpose(x, (1, 2, 0)), spec, fuse=fuse,
                            bc_value=BC)
    return jnp.transpose(y, (2, 0, 1))


def _reference(x, spec, fuse):
    return jax.vmap(lambda g: jacobi_reference(
        g, spec, DirichletBC(BC), fuse))(x.astype(jnp.float32))


def _bf16_ulp(v):
    """One bfloat16 ulp at each of ``v``'s values (8 significant bits)."""
    mag = np.maximum(np.abs(np.asarray(v, np.float64)), 2.0 ** -100)
    return 2.0 ** (np.floor(np.log2(mag)) - 7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("fuse", [1, 4, 16])
@pytest.mark.parametrize("tile", [(16, 16), (32, 64), (64, 64)],
                         ids=["16x16", "32x64", "64x64"])
@pytest.mark.parametrize("batch", [128, 300])
def test_agrees_with_fused_step_and_reference(batch, tile, fuse, dtype):
    x = _batch((batch, *tile), dtype)
    got = _lanes(x, SPEC, fuse)
    assert got.shape == x.shape and got.dtype == x.dtype
    cols = jacobi2d_fused_step(x, SPEC, fuse=fuse, bc_value=BC)
    want = _reference(x, SPEC, fuse)
    got32 = np.asarray(got, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got32, np.asarray(cols), rtol=0, atol=1e-6)
        np.testing.assert_allclose(got32, np.asarray(want), rtol=0, atol=1e-6)
    else:
        # both kernels sweep in float32 and round once, at the call's end
        np.testing.assert_array_equal(got32, np.asarray(cols, np.float32))
        assert np.all(np.abs(got32 - np.asarray(want))
                      <= _bf16_ulp(want))


@pytest.mark.parametrize("spec", [star(2, [0.1, 0.05], center=0.4),
                                  star(2, [0.2, 0.05])],
                         ids=["star-r2-center", "star-r2"])
def test_radius_two(spec):
    # tiles never meet across lanes, so a deeper rim is as exact as r = 1
    x = _batch((130, 16, 24), jnp.float32, seed=1)
    got = _lanes(x, spec, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _reference(x, spec, 3)), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        jacobi2d_fused_step(x, spec, fuse=3, bc_value=BC)), rtol=0, atol=1e-6)


def test_lane_blocks():
    # whole 128-lane groups, at least one, within the block budget, and
    # never more than the batch's whole groups
    assert lane_block(131072, 64, 64) == 128
    assert lane_block(4096, 32, 64) == 128
    assert lane_block(300, 16, 16) == 256
    assert lane_block(4096, 16, 16) == 1024
    assert lane_block(4096, 64, 64, itemsize=2) == 128


@pytest.mark.parametrize("batch, tile, fuse", [
    (256, (64, 64), 4), (300, (16, 16), 2), (300, (32, 64), 1)])
def test_jacobi2d_takes_the_lanes(batch, tile, fuse):
    # a plan routes these batches to the batch-minor kernel through the
    # public wrapper, and its scan of calls computes the oracle's sweeps
    x = _batch((batch, *tile), jnp.float32, seed=2)
    assert lane_axis(x.shape, SPEC, BC, budget=BUDGET) == "batch"
    plan = make_plan(SPEC, tile, backend="pallas", bc=BC, iters=3 * fuse,
                     fuse=fuse)
    assert plan.lane_axis(batch) == "batch"
    got = plan(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _reference(x, SPEC, 3 * fuse)), rtol=0, atol=1e-6)
    for run in (lambda v: plan(v), lambda v: jacobi2d(
            v, SPEC, bc_value=BC, iterations=3 * fuse, fuse=fuse,
            lane_axis="batch")):
        text = jax.jit(run).lower(x).as_text()
        assert "jacobi2d_lanes_step" in text
        assert "jacobi2d_fused_step" not in text


@pytest.mark.parametrize("shape", [(131072, 64, 64), (4096, 64, 64),
                                   (300, 16, 16), (128, 32, 64)])
def test_rule_puts_the_batch_on_the_lanes(shape):
    assert lane_axis(shape, SPEC, BC, budget=BUDGET) == "batch"
    assert lane_axis(shape, SPEC, BC, itemsize=2, budget=BUDGET) == "batch"
    plan = make_plan(SPEC, shape[1:], backend="pallas", bc=BC, iters=4,
                     fuse=4, device_kind=V5E)
    assert plan.lane_axis(shape[0]) == "batch"


KAPPA = 1.0 + np.random.default_rng(3).random((64, 64), np.float32)


@pytest.mark.parametrize("shape, spec, bc", [
    ((1, 16384, 16384), SPEC, BC),         # the grid: lanes already dense
    ((64, 64, 64), SPEC, BC),              # under one 128-lane group
    ((127, 16, 16), SPEC, BC),
    ((256, 64, 128), SPEC, BC),            # 128-wide rows fill their lanes
    ((129, 64, 200), SPEC, BC),            # rows fill more than the batch
    ((4096, 256, 200), SPEC, BC),          # a block over the VMEM budget
    ((4096, 80, 64), SPEC, BC),
    ((4096, 64, 64), star(2, [0.2, 0.05]), BC),          # more W shifts
    ((4096, 64, 64), heterogeneous_jacobi(KAPPA), BC),   # variable taps
    ((4096, 64, 64), SPEC, None),          # raw steps, no Dirichlet pin
    ((4096, 10, 64, 64), laplace_jacobi(3), BC),         # 3D
], ids=["grid", "batch-64", "batch-127", "rows-128", "rows-200", "vmem",
        "vmem-80-rows", "vmem-radius-2", "variable", "raw", "3d"])
def test_rule_keeps_the_rows_on_the_lanes(shape, spec, bc):
    assert lane_axis(shape, spec, bc, budget=BUDGET) == "cols"


@pytest.mark.parametrize("grid, backend, bc, batch, want", [
    ((16384, 16384), "pallas", BC, 1, "cols"),
    ((64, 64), "pallas", None, 4096, "cols"),
    ((64, 64), "reference", BC, 4096, None),
    ((10, 64, 64), "pallas", BC, 4096, None),
], ids=["grid", "raw", "reference", "3d"])
def test_plan_records_the_lane_axis(grid, backend, bc, batch, want):
    spec = laplace_jacobi(len(grid))
    plan = make_plan(spec, grid, backend=backend, bc=bc, iters=4,
                     device_kind=V5E)
    assert plan.lane_axis(batch) == want


def test_3d_and_small_batches_keep_their_kernels():
    # jacobi3d never asks the rule; a 2D batch under 128 runs the row kernel
    x = _batch((64, 16, 16), jnp.float32)
    plan = make_plan(SPEC, (16, 16), backend="pallas", bc=BC, iters=4,
                     fuse=4)
    for run in (lambda v: plan(v), lambda v: jacobi2d(
            v, SPEC, bc_value=BC, iterations=4, fuse=4)):
        text = jax.jit(run).lower(x).as_text()
        assert "jacobi2d_fused_step" in text and "lanes" not in text
    x3 = _batch((128, 4, 8, 8), jnp.float32)
    text = jax.jit(lambda v: jacobi3d(v, laplace_jacobi(3), bc_value=BC,
                                      iterations=2)).lower(x3).as_text()
    assert "lanes" not in text
