"""Direct unit tests for parallel/halo.py and the deep-halo accounting.

The exchange primitives were previously covered only indirectly through the
distributed solver; these tests pin their contracts down: ``exchange_1d``
halo extents and non-wrapping zero edges at radius >= 2, the corner-transit
property of the two-phase ``exchange_halo_2d`` (the augmented tile equals a
window of the zero-padded global grid, diagonal-neighbour values included),
and the depth guard.  Subprocess cases use the ``run_with_devices`` fixture
(8 forced host devices); the analytic accounting and runner validation run
in-process on the 1x1 mesh.
"""
import jax
import pytest

from repro.core.distributed import (
    HALO_PHASES_PER_EXCHANGE,
    halo_comm_rounds,
    make_halo_runner,
    max_halo_fuse,
)
from repro.core.stencil import laplace_jacobi, star


class TestCommAccounting:
    def test_rounds_drop_by_fuse_depth(self):
        assert halo_comm_rounds(16, 1) == 16 * HALO_PHASES_PER_EXCHANGE
        assert halo_comm_rounds(16, 2) == 8 * HALO_PHASES_PER_EXCHANGE
        assert halo_comm_rounds(16, 4) == 4 * HALO_PHASES_PER_EXCHANGE
        assert halo_comm_rounds(16, 16) == HALO_PHASES_PER_EXCHANGE

    def test_partial_chunk_rounds_up(self):
        # 5 iterations at fuse 2 still need 3 exchanges.
        assert halo_comm_rounds(5, 2) == 3 * HALO_PHASES_PER_EXCHANGE

    def test_variable_specs_pay_one_field_exchange(self):
        assert (halo_comm_rounds(8, 2, variable=True)
                == halo_comm_rounds(8, 2) + HALO_PHASES_PER_EXCHANGE)

    def test_max_fuse_bounded_by_local_tile(self):
        assert max_halo_fuse(1, 8, 8) == 8
        assert max_halo_fuse(2, 8, 8) == 4
        assert max_halo_fuse(1, 8, 6) == 6
        # degenerate tiles still allow the unfused schedule
        assert max_halo_fuse(3, 2, 2) == 1

    def test_exchange_bytes_scale_with_perimeter(self):
        from repro.kernels.tiling import halo_exchange_bytes
        b1 = halo_exchange_bytes((64, 64), 1, 1)
        b2 = halo_exchange_bytes((128, 128), 1, 1)
        assert b1 == 2 * 1 * (64 + 64 + 2) * 4
        # doubling the tile edge roughly doubles (not quadruples) the bytes
        assert 1.9 < b2 / b1 < 2.1
        # deeper halos move proportionally more per exchange
        assert halo_exchange_bytes((64, 64), 4, 1) > \
            3 * halo_exchange_bytes((64, 64), 1, 1)


class TestRunnerValidation:
    """make_halo_runner's fuse/depth checks (1x1 mesh, in-process)."""

    def _mesh(self):
        return jax.make_mesh((1, 1), ("data", "model"))

    def test_fuse_must_divide_iterations(self):
        with pytest.raises(ValueError, match="not divisible"):
            make_halo_runner(self._mesh(), laplace_jacobi(2), H=8, W=8,
                             bc_value=0.0, iterations=5, fuse=2)

    def test_fuse_must_be_positive(self):
        with pytest.raises(ValueError, match="fuse"):
            make_halo_runner(self._mesh(), laplace_jacobi(2), H=8, W=8,
                             bc_value=0.0, iterations=4, fuse=0)

    def test_halo_depth_bounded_by_local_tile(self):
        with pytest.raises(ValueError, match="max fuse"):
            make_halo_runner(self._mesh(), laplace_jacobi(2), H=8, W=8,
                             bc_value=0.0, iterations=16, fuse=16)

    def test_radius2_halves_the_depth_budget(self):
        spec = star(2, [0.15, 0.05], center=0.2)
        with pytest.raises(ValueError, match="max fuse"):
            make_halo_runner(self._mesh(), spec, H=8, W=8, bc_value=0.0,
                             iterations=8, fuse=8)  # R = 16 > 8
        make_halo_runner(self._mesh(), spec, H=8, W=8, bc_value=0.0,
                         iterations=8, fuse=4)      # R = 8 fits


@pytest.mark.slow
class TestExchange1D:
    def test_radius2_extents_and_nonwrapping_zero_edges(
            self, run_with_devices):
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.halo import exchange_1d

        n, loc, r = 4, 4, 2
        mesh = jax.make_mesh((n,), ("x",))
        g = jnp.arange(1, n * loc + 1, dtype=jnp.float32)  # no zeros inside

        def f(xl):
            lo, hi = exchange_1d(xl, "x", n, 0, r)
            assert lo.shape == hi.shape == (r,)
            return jnp.concatenate([lo, hi])

        halos = np.asarray(jax.shard_map(
            f, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
            check_vma=False)(g)).reshape(n, 2 * r)
        gp = np.pad(np.asarray(g), r)  # zero-padded global line
        for i in range(n):
            np.testing.assert_array_equal(halos[i, :r],
                                          gp[i * loc: i * loc + r])
            np.testing.assert_array_equal(
                halos[i, r:], gp[(i + 1) * loc + r: (i + 1) * loc + 2 * r])
        # edge shards saw literal zeros, not wrapped values
        assert (halos[0, :r] == 0).all() and (halos[-1, r:] == 0).all()
        print("ex1d ok")
        """)
        assert "ex1d ok" in out

    def test_depth_beyond_local_extent_rejected(self, run_with_devices):
        out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel.halo import exchange_1d

        n, loc = 4, 4
        mesh = jax.make_mesh((n,), ("x",))
        g = jnp.zeros((n * loc,), jnp.float32)
        try:
            jax.shard_map(
                lambda xl: exchange_1d(xl, "x", n, 0, loc + 1)[0],
                mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                check_vma=False)(g)
        except ValueError as e:
            assert "exceeds the local extent" in str(e), e
            print("depth-guard ok")
        """)
        assert "depth-guard ok" in out


@pytest.mark.slow
class TestExchange2D:
    def test_corner_transit_and_deep_halo_window(self, run_with_devices):
        # The two-phase exchange must deliver the exact window of the
        # zero-padded global grid — including the corner cells that only a
        # diagonal neighbour owns (they transit through the row phase) —
        # at radius 2 and at the deepest legal halo (r == local extent,
        # where one phase forwards a whole neighbouring tile).
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.halo import exchange_halo_2d

        nr, nc = 2, 4
        H, W = 8, 16
        hl, wl = H // nr, W // nc
        g = jnp.arange(1, H * W + 1, dtype=jnp.float32).reshape(H, W)
        mesh = jax.make_mesh((nr, nc), ("row", "col"))

        for r in (2, min(hl, wl)):
            gp = jnp.pad(g, r)

            def f(xl):
                aug = exchange_halo_2d(xl, "row", "col", nr, nc, r)
                ri = jax.lax.axis_index("row")
                ci = jax.lax.axis_index("col")
                want = jax.lax.dynamic_slice(
                    gp, (ri * hl, ci * wl), (hl + 2 * r, wl + 2 * r))
                return jnp.all(aug == want)[None, None]

            ok = jax.shard_map(f, mesh=mesh, in_specs=(P("row", "col"),),
                               out_specs=P("row", "col"),
                               check_vma=False)(g)
            assert np.asarray(ok).all(), f"r={r}"
        print("corner ok")
        """)
        assert "corner ok" in out
