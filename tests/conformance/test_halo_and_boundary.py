"""Halo-exchange edge semantics and boundary-mode equivalence, exercised
through the unified ``stencil_apply`` dispatcher.

Multi-device cases run in a subprocess (the ``run_with_devices`` fixture
from tests/conftest.py) with XLA_FLAGS=--xla_force_host_platform_device_count
so the main pytest process keeps its single-device view.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BoundaryMode,
    DirichletBC,
    jacobi_reference,
    laplace_jacobi,
    star,
    stencil_apply,
)

RNG = np.random.default_rng(3)


class TestBoundaryModeEquivalence:
    """MASK ≡ PAD ≡ MATRIX: three BC encodings, one operator (boundary.py)."""

    def test_all_modes_agree_on_same_grid(self):
        spec = laplace_jacobi(2)
        x = jnp.asarray(RNG.standard_normal((2, 16, 12)), jnp.float32)
        outs = {
            "conv+mask": stencil_apply(spec, x, backend="conv", bc=2.5,
                                       mode=BoundaryMode.MASK, iters=5),
            "conv+pad": stencil_apply(spec, x, backend="conv", bc=2.5,
                                      mode=BoundaryMode.PAD, iters=5),
            "dense+matrix": stencil_apply(spec, x, backend="dense", bc=2.5,
                                          mode=BoundaryMode.MATRIX, iters=5),
            "pallas+mask": stencil_apply(spec, x, backend="pallas", bc=2.5,
                                         mode=BoundaryMode.MASK, iters=5),
        }
        ref = jnp.stack([jacobi_reference(x[i], spec, DirichletBC(2.5), 5)
                         for i in range(2)])
        for name, out in outs.items():
            np.testing.assert_allclose(out, ref, atol=1e-5, err_msg=name)

    def test_modes_agree_for_negative_bc(self):
        spec = laplace_jacobi(2)
        x = jnp.asarray(RNG.standard_normal((1, 10, 14)), jnp.float32)
        a = stencil_apply(spec, x, backend="conv", bc=-3.0,
                          mode=BoundaryMode.MASK, iters=4)
        b = stencil_apply(spec, x, backend="conv", bc=-3.0,
                          mode=BoundaryMode.PAD, iters=4)
        c = stencil_apply(spec, x, backend="dense", bc=-3.0,
                          mode=BoundaryMode.MATRIX, iters=4)
        np.testing.assert_allclose(a, b, atol=1e-5)
        np.testing.assert_allclose(b, c, atol=1e-5)


class TestHaloSingleDevice:
    """The halo backend degenerates gracefully to a 1x1 mesh in-process."""

    def test_halo_matches_oracle_single_device(self):
        spec = laplace_jacobi(2)
        x = jnp.asarray(RNG.standard_normal((2, 16, 8)), jnp.float32)
        out = stencil_apply(spec, x, backend="halo", bc=1.5, iters=4)
        ref = jnp.stack([jacobi_reference(x[i], spec, DirichletBC(1.5), 4)
                         for i in range(2)])
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_halo_radius2_single_device(self):
        spec = star(2, [0.1, 0.05], center=0.3)
        x = jnp.asarray(RNG.standard_normal((1, 12, 16)), jnp.float32)
        out = stencil_apply(spec, x, backend="halo", bc=0.5, iters=3)
        ref = jnp.stack([jacobi_reference(x[0], spec, DirichletBC(0.5), 3)])
        np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.slow
class TestHaloMultiDevice:
    def test_edge_permutes_deliver_zeros(self, run_with_devices):
        # Non-wrapping ppermute: the halo a mesh-edge device receives from
        # "outside" the mesh must be zeros (the oracle's zero-pad semantics).
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.halo import exchange_halo_2d

        mesh = jax.make_mesh((2, 4), ("row", "col"))
        H, W, r = 8, 16, 2
        x = jnp.asarray(np.arange(1, H * W + 1, dtype=np.float32).reshape(H, W))

        def gather_padded(xl):
            xp = exchange_halo_2d(xl, "row", "col", 2, 4, r)
            # re-assemble the halo-augmented tiles for inspection
            return xp[None]

        fn = jax.shard_map(gather_padded, mesh=mesh,
                           in_specs=(P("row", "col"),),
                           out_specs=P(None, "row", "col"), check_vma=False)
        tiles = np.asarray(fn(x))  # (1, 2*(4+2r), 4*(4+2r))
        th, tw = H // 2 + 2 * r, W // 4 + 2 * r
        tiles = tiles[0].reshape(2, th, 4, tw).transpose(0, 2, 1, 3)

        # Global top edge: row-0 tiles' low halo rows are all zero.
        assert np.all(tiles[0, :, :r, :] == 0.0)
        # Global bottom edge: row-1 tiles' high halo rows are all zero.
        assert np.all(tiles[1, :, -r:, :] == 0.0)
        # Global left/right edges likewise.
        assert np.all(tiles[:, 0, :, :r] == 0.0)
        assert np.all(tiles[:, 3, :, -r:] == 0.0)
        # Interior seams carry the true neighbour values, not zeros: tile
        # (0,1)'s left halo is tile (0,0)'s rightmost r columns.
        xnp = np.asarray(x)
        np.testing.assert_array_equal(tiles[0, 1, r:-r, :r],
                                      xnp[0:4, 4 - r:4])
        print("edge zeros ok")
        """)
        assert "edge zeros ok" in out

    def test_stencil_apply_halo_on_device_mesh(self, run_with_devices):
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DirichletBC, jacobi_reference, laplace_jacobi
        from repro.core.plan import stencil_apply

        mesh = jax.make_mesh((4, 2), ("row", "col"))
        spec = laplace_jacobi(2)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32)
        out = stencil_apply(spec, x, backend="halo", bc=1.5, iters=5,
                            mesh=mesh)
        ref = jnp.stack([jacobi_reference(x[i], spec, DirichletBC(1.5), 5)
                         for i in range(2)])
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-5, err
        print("halo mesh ok", err)
        """)
        assert "halo mesh ok" in out

    def test_halo_support_rejects_untileable_grid(self, run_with_devices):
        out = run_with_devices("""
        import jax
        from repro.core import backend_support, laplace_jacobi

        mesh = jax.make_mesh((4, 2), ("row", "col"))
        sup = backend_support("halo", laplace_jacobi(2), grid_shape=(15, 8),
                              mesh=mesh)
        assert not sup.ok and "tile" in sup.reason, sup
        print("reject ok")
        """)
        assert "reject ok" in out
