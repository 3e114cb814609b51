"""Cross-backend conformance matrix for the unified stencil dispatcher.

Walks every cell of (stencil family × ndim) × backend × boundary mode ×
dtype and asserts the backend matches the NumPy/jnp oracle within dtype
tolerance — or is *explicitly* skipped with the reason string that
``backend_support`` reports.  This is the executable form of the paper's
central claim: every tensor-program encoding of a stencil computes the same
operator.

Pallas cells run in interpret mode on CPU (the kernels auto-select it), so
the whole matrix passes on CPU CI.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BACKENDS,
    BoundaryMode,
    DirichletBC,
    backend_support,
    box,
    causal_conv1d_spec,
    choose_schedule,
    heterogeneous_jacobi,
    jacobi_reference,
    laplace_jacobi,
    make_plan,
    star,
    stencil_apply,
    variable_coefficient,
)

RNG = np.random.default_rng(20260802)

ITERS = 2
BC_VALUE = 1.5

# Small odd-shaped grids: exercise block padding without slowing interpret mode.
GRIDS = {1: (33,), 2: (12, 17), 3: (6, 10, 12)}


def _kappa(ndim):
    """A smooth positive conductivity field matching the test grid."""
    return 1.0 + 9.0 * RNG.random(GRIDS[ndim]).astype(np.float32)


SPECS = {
    "laplace/1d": laplace_jacobi(1),
    "laplace/2d": laplace_jacobi(2),
    "laplace/3d": laplace_jacobi(3),
    "star_r2/1d": star(1, [0.15, 0.05], center=0.2),
    "star_r2/2d": star(2, [0.15, 0.05], center=0.2),
    "star_r2/3d": star(3, [0.15, 0.05], center=0.2),
    "box/1d": box(1),
    "box/2d": box(2),
    "box/3d": box(3),
    "causal_conv1d/1d": causal_conv1d_spec([0.1, 0.2, 0.3, 0.4]),
    # Variable-coefficient cells: every tap carries a per-cell weight field
    # (heterogeneous diffusion), or a mix of scalar and per-cell taps.
    "varcoef/1d": heterogeneous_jacobi(_kappa(1)),
    "varcoef/2d": heterogeneous_jacobi(_kappa(2)),
    "varcoef/3d": heterogeneous_jacobi(_kappa(3)),
    "varcoef_mixed/2d": variable_coefficient(
        laplace_jacobi(2),
        {(0, 1): 0.25 + 0.1 * RNG.random(GRIDS[2]).astype(np.float32)},
        name="varmix2d"),
}

MODES = (BoundaryMode.MASK, BoundaryMode.PAD, BoundaryMode.MATRIX)
# Every backend one sweep a pass, and the 2D Pallas kernel once more at a
# fused depth (the temporally-blocked trapezoid).  Depths are explicit, so a
# change of the default depth cannot change what a case runs.
BACKEND_DEPTHS = [pytest.param(b, 1, id=b) for b in BACKENDS] + [
    pytest.param("pallas", ITERS, id=f"pallas-fuse{ITERS}")]
NO_3D_FUSION = "temporal fusion is 2D only (jacobi_fused.py)"
DTYPES = {"f32": (jnp.float32, 2e-5), "bf16": (jnp.bfloat16, 6e-2)}


def _oracle(spec, x):
    bc = DirichletBC(BC_VALUE)
    return jnp.stack([jacobi_reference(x[i].astype(jnp.float32), spec, bc,
                                       ITERS) for i in range(x.shape[0])])


@pytest.mark.slow
@pytest.mark.parametrize("dtype_name", list(DTYPES))
@pytest.mark.parametrize("mode", MODES, ids=lambda m: m.value)
@pytest.mark.parametrize("backend, fuse", BACKEND_DEPTHS)
@pytest.mark.parametrize("family", list(SPECS))
def test_matrix_cell(family, backend, fuse, mode, dtype_name):
    spec = SPECS[family]
    grid = GRIDS[spec.ndim]
    dtype, atol = DTYPES[dtype_name]

    sup = backend_support(backend, spec, grid_shape=grid, mode=mode,
                          bc=BC_VALUE)
    if not sup:
        pytest.skip(f"{backend}/{family}/{mode.value}: {sup.reason}")
    if fuse > 1 and spec.ndim != 2:
        pytest.skip(f"{backend}/{family}: {NO_3D_FUSION}")

    x = jnp.asarray(RNG.standard_normal((2, *grid)), dtype)
    plan = make_plan(spec, grid, backend=backend, bc=BC_VALUE, mode=mode,
                     iters=ITERS, fuse=fuse, dtype=dtype)
    assert plan.fuse == fuse
    out = plan(x)
    assert out.dtype == dtype
    ref = _oracle(spec, x)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=atol,
                               err_msg=f"{backend} diverges from oracle on "
                                       f"{family} {mode.value} {dtype_name}")


class TestRawZeroPad:
    """bc=None cells: raw repeated application with implicit zero padding."""

    @pytest.mark.parametrize("backend, fuse", [
        ("reference", 1), ("pallas", 1), ("pallas", ITERS)],
        ids=["reference", "pallas", f"pallas-fuse{ITERS}"])
    @pytest.mark.parametrize("ndim", [2, 3])
    def test_raw_matches_oracle(self, backend, fuse, ndim):
        spec = laplace_jacobi(ndim)
        sup = backend_support(backend, spec, grid_shape=GRIDS[ndim], bc=None)
        if not sup:
            pytest.skip(sup.reason)
        if fuse > 1 and ndim != 2:
            pytest.skip(NO_3D_FUSION)
        x = jnp.asarray(RNG.standard_normal((1, *GRIDS[ndim])), jnp.float32)
        plan = make_plan(spec, GRIDS[ndim], backend=backend, bc=None,
                         iters=ITERS, fuse=fuse)
        assert plan.fuse == fuse
        out = plan(x)
        ref = stencil_apply(spec, x, backend="reference", bc=None, iters=ITERS)
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestAutoBackend:
    """Acceptance: backend="auto" is oracle-identical on the paper benchmarks."""

    def test_auto_2d_paper_benchmark(self):
        # Paper Table 1 shape: X=Y=64, Dirichlet BC = 1.0.
        spec = laplace_jacobi(2)
        x = jnp.asarray(RNG.standard_normal((2, 64, 64)), jnp.float32)
        out = stencil_apply(spec, x, backend="auto", bc=1.0, iters=10)
        ref = jnp.stack([jacobi_reference(x[i], spec, DirichletBC(1.0), 10)
                         for i in range(2)])
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_auto_3d_paper_benchmark(self):
        # Paper Fig 6 shape: (Z, X, Y) = (10, 64, 64).
        spec = laplace_jacobi(3)
        x = jnp.asarray(RNG.standard_normal((1, 10, 64, 64)), jnp.float32)
        out = stencil_apply(spec, x, backend="auto", bc=1.0, iters=4)
        ref = jnp.stack([jacobi_reference(x[0], spec, DirichletBC(1.0), 4)])
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_auto_choice_is_supported_and_deterministic(self):
        spec = laplace_jacobi(2)
        a = choose_schedule(spec, (64, 64), chunk=20)
        b = choose_schedule(spec, (64, 64), chunk=20)
        assert a == b
        assert backend_support(a.backend, spec, grid_shape=(64, 64)).ok
        assert a.costs[a.backend] == min(a.costs.values())

    def test_auto_cost_model_device_kinds(self):
        # CPU must never pick interpret-mode Pallas; TPU should exploit
        # temporal fusion for iteration-heavy 2D runs (DESIGN §2).
        spec = laplace_jacobi(2)
        cpu = choose_schedule(spec, (64, 64), chunk=20, device_kind="cpu")
        assert cpu.backend != "pallas"
        tpu = choose_schedule(spec, (64, 64), chunk=20,
                              device_kind="TPU v5 lite")
        assert tpu.backend == "pallas" and tpu.fuse > 1

    def test_auto_1d_falls_back_to_a_legal_backend(self):
        spec = causal_conv1d_spec([0.1, 0.2, 0.3, 0.4])
        name = choose_schedule(spec, (64,), chunk=4).backend
        assert backend_support(name, spec, grid_shape=(64,)).ok


class TestDispatcherContract:
    def test_unbatched_input_round_trips(self):
        spec = laplace_jacobi(2)
        x = jnp.asarray(RNG.standard_normal((12, 17)), jnp.float32)
        out = stencil_apply(spec, x, backend="conv", bc=1.0, iters=2)
        assert out.shape == x.shape
        ref = jacobi_reference(x, spec, DirichletBC(1.0), 2)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_unknown_backend_rejected(self):
        spec = laplace_jacobi(2)
        x = jnp.zeros((1, 8, 8), jnp.float32)
        for name in ("tensorflow", "pallas_fused"):
            with pytest.raises(ValueError, match="unknown backend") as err:
                stencil_apply(spec, x, backend=name)
            assert all(b in str(err.value) for b in BACKENDS)
        assert len(BACKENDS) == 6

    def test_unsupported_cell_raises_with_reason(self):
        spec = star(2, [0.1, 0.05])  # radius 2
        x = jnp.zeros((1, 12, 12), jnp.float32)
        with pytest.raises(ValueError, match="radius-1"):
            stencil_apply(spec, x, backend="conv", bc=1.0,
                          mode=BoundaryMode.PAD)

    def test_grid_rank_mismatch_rejected(self):
        with pytest.raises(ValueError, match="incompatible"):
            stencil_apply(laplace_jacobi(3), jnp.zeros((4, 4), jnp.float32))

    def test_every_skip_reason_is_nonempty(self):
        # The conformance matrix depends on reasons being real sentences.
        for name, spec in SPECS.items():
            for b in BACKENDS:
                for m in MODES:
                    sup = backend_support(b, spec, grid_shape=GRIDS[spec.ndim],
                                          mode=m, bc=BC_VALUE)
                    if not sup:
                        assert len(sup.reason) > 10, (name, b, m)


class TestVariableCoefficientSupport:
    """The variable-coefficient cells that cannot run must say why."""

    def test_pallas_fused_variable_coefficients_are_live(self):
        # Earlier the fused kernel rejected var specs (the fields would have
        # needed halo replication); they now stream as a halo-replicated
        # operand sliced per in-kernel iteration, so the cell is live — and
        # must match the oracle at a fuse depth > 1.
        spec = SPECS["varcoef/2d"]
        sup = backend_support("pallas", spec, grid_shape=GRIDS[2],
                              bc=BC_VALUE)
        assert sup.ok, sup.reason
        x = jnp.asarray(RNG.standard_normal((2, *GRIDS[2])), jnp.float32)
        out = stencil_apply(spec, x, backend="pallas", bc=BC_VALUE,
                            iters=ITERS, fuse=ITERS)
        np.testing.assert_allclose(out, _oracle(spec, x), atol=2e-5)

    def test_halo_variable_coefficients_are_live(self):
        # PR 3 left this cell as a reasoned skip; the fields now shard with
        # the grid and are halo-exchanged once per chunk, so the cell is
        # live — and must match the oracle (1x1 mesh runs in-process).
        spec = SPECS["varcoef/2d"]
        sup = backend_support("halo", spec, grid_shape=GRIDS[2], bc=BC_VALUE)
        assert sup.ok, sup.reason
        x = jnp.asarray(RNG.standard_normal((2, *GRIDS[2])), jnp.float32)
        out = stencil_apply(spec, x, backend="halo", bc=BC_VALUE, iters=ITERS,
                            fuse=1)
        np.testing.assert_allclose(out, _oracle(spec, x), atol=2e-5)

    def test_conv_3d_channels_reports_reasoned_skip(self):
        spec = SPECS["varcoef/3d"]
        sup = backend_support("conv", spec, grid_shape=GRIDS[3], bc=BC_VALUE)
        assert not sup and "channels-trick" in sup.reason

    def test_mismatched_field_shape_rejected_everywhere(self):
        spec = SPECS["varcoef/2d"]
        for b in BACKENDS:
            sup = backend_support(b, spec, grid_shape=(8, 8), bc=BC_VALUE)
            assert not sup and "weight fields" in sup.reason, b

    def test_supported_variable_cells_cover_all_dims(self):
        # Every varcoef family must have at least one real (non-oracle)
        # backend per ndim, or the matrix would silently test nothing.
        for name in ("varcoef/1d", "varcoef/2d", "varcoef/3d",
                     "varcoef_mixed/2d"):
            spec = SPECS[name]
            legal = [b for b in BACKENDS if b != "reference" and any(
                backend_support(b, spec, grid_shape=GRIDS[spec.ndim],
                                mode=m, bc=BC_VALUE) for m in MODES)]
            assert legal, name
