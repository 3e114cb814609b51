"""Cost-model regression tier: the roofline model's ordering must stay
consistent with (a) the paper's §4 accounting and (b) the timings actually
recorded on this host in ``BENCH_stencil.json`` — so silent roofline drift
(constants edited, FLOP accounting broken, auto picking a regressed backend)
gets caught by CI instead of by a slow benchmark run.
"""
import json
import os

import pytest

from repro.core import box, choose_backend, laplace_jacobi
from repro.core.plan import DEVICE_PROFILES, estimate_seconds
from repro.core.solver import select_fuse

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_PATH = os.path.join(REPO, "BENCH_stencil.json")

TABLE1_GRID = (64, 64)
TABLE1_ITERS = 100


def _load_bench() -> dict:
    if not os.path.exists(BENCH_PATH):
        pytest.skip("no BENCH_stencil.json recorded on this host "
                    "(run scripts/ci.sh)")
    with open(BENCH_PATH) as f:
        data = json.load(f)
    if "solver" not in data:
        pytest.skip("BENCH_stencil.json predates the solver-metrics schema "
                    "(schema >= 2); re-run scripts/ci.sh")
    return data


class TestRooflineModel:
    """Analytic assertions — no recorded artifact needed."""

    def test_dense_much_costlier_than_conv(self):
        # Paper §4: 8191 vs 17 FLOPs/point, plus the N^2 matrix re-stream.
        spec = laplace_jacobi(2)
        cpu = DEVICE_PROFILES["cpu"]
        dense = estimate_seconds("dense", spec, TABLE1_GRID, TABLE1_ITERS, cpu)
        conv = estimate_seconds("conv", spec, TABLE1_GRID, TABLE1_ITERS, cpu)
        assert dense > 10 * conv, (dense, conv)

    def test_auto_picks_conv_for_fp32_table1_shape_on_cpu(self):
        name, costs = choose_backend(laplace_jacobi(2), TABLE1_GRID,
                                     iters=TABLE1_ITERS, device_kind="cpu")
        assert name == "conv", costs

    def test_fuse_depth_pricing_is_monotone_while_memory_bound(self):
        # On the TPU profile a large 2D Jacobi is HBM-bound: each doubling of
        # the fuse depth halves traffic and must price cheaper.
        spec = laplace_jacobi(2)
        tpu = DEVICE_PROFILES["TPU v5 lite"]
        ests = [estimate_seconds("pallas_fused", spec, (512, 512), 64, tpu,
                                 fuse=f) for f in (1, 2, 4, 8)]
        assert ests == sorted(ests, reverse=True), ests

    @pytest.mark.parametrize("grid, want", [((512, 512), 8),
                                            ((4096, 16384), 4)],
                             ids=["fits", "vmem-bound"])
    def test_default_fuse_priced_is_fuse_planned(self, grid, want):
        # fuse=None prices the depth make_plan resolves, which the VMEM
        # budget lowers on wide rows of a radius-2 star (8 does not fit).
        from repro.core import make_plan, star
        spec = star(2, [0.1, 0.1])
        plan = make_plan(spec, grid, backend="pallas_fused", bc=1.0,
                         iters=16, device_kind="TPU v5 lite",
                         interpret=True, tuned=None)
        assert plan.fuse == want
        tpu = DEVICE_PROFILES["TPU v5 lite"]
        assert estimate_seconds("pallas_fused", spec, grid, 16, tpu) == \
            estimate_seconds("pallas_fused", spec, grid, 16, tpu, fuse=want)

    def test_fuse_pricing_includes_rim_recompute(self):
        # Deeper fusion is NOT free on a grid that tiles into row blocks:
        # compute time must grow with depth even as memory time shrinks
        # (the trapezoid redundancy factor).  A grid that fits one block
        # runs resident and recomputes nothing at any depth.
        from repro.kernels.tiling import fuse_redundancy
        r1 = fuse_redundancy((4096, 4096), 1, 1)
        r8 = fuse_redundancy((4096, 4096), 8, 1)
        assert 1.0 <= r1 < r8
        assert fuse_redundancy((64, 64), 8, 1) == 1.0

    def test_halo_comm_term_scales_with_perimeter(self):
        # The halo communication term is O(perimeter), not O(area): doubling
        # the grid edge (4x the area) must roughly double the per-exchange
        # wire bytes on a fixed mesh.
        from repro.kernels.tiling import halo_exchange_bytes
        small = halo_exchange_bytes((64, 64), 1, 1)
        big = halo_exchange_bytes((128, 128), 1, 1)
        assert 1.9 < big / small < 2.1, (small, big)
        # and the priced totals preserve that ordering on a slow link
        spec = laplace_jacobi(2)
        cpu = DEVICE_PROFILES["cpu"]
        comm = [estimate_seconds("halo", spec, (g, g), 64, cpu,
                                 mesh_shape=(2, 4)) for g in (64, 128, 256)]
        assert comm == sorted(comm), comm

    def test_halo_fuse_pricing_drops_roughly_one_over_fuse(self):
        # Latency-dominated cell (small tile, cpu collective profile): the
        # per-exchange cost amortizes over fuse local steps, so pricing must
        # be monotone decreasing in depth — the communication-avoiding win.
        spec = laplace_jacobi(2)
        cpu = DEVICE_PROFILES["cpu"]
        ests = [estimate_seconds("halo", spec, (64, 64), 16, cpu, fuse=f,
                                 mesh_shape=(2, 4)) for f in (1, 2, 4, 8)]
        assert ests == sorted(ests, reverse=True), ests
        # the drop tracks ~1/fuse while the latency term dominates
        assert ests[1] < 0.75 * ests[0], ests

    def test_halo_fuse1_pricing_keeps_the_legacy_latency_floor(self):
        # fuse=1 on an unsharded (1x1) mesh must reproduce the pre-fusion
        # model exactly: per-iter roofline + 1e-5s of permute latency per
        # iteration — the backward-compatibility anchor for old cost tables.
        spec = laplace_jacobi(2)
        cpu = DEVICE_PROFILES["cpu"]
        body = estimate_seconds("reference", spec, (64, 64), 16, cpu)
        halo = estimate_seconds("halo", spec, (64, 64), 16, cpu)
        assert abs(halo - (body + 1e-5 * 16)) < 1e-12, (halo, body)

    def test_select_fuse_picks_deep_halo_on_latency_dominated_cells(self):
        spec = laplace_jacobi(2)
        f = select_fuse("halo", spec, (64, 64), 16, "cpu", tuned=None,
                        mesh=(2, 4))
        assert f is not None and f > 1, f
        # the depth is clamped to what the local tile can host
        f_small = select_fuse("halo", spec, (8, 8), 16, "cpu", tuned=None,
                              mesh=(2, 4))
        assert f_small is not None and f_small * spec.radius <= 2, f_small

    def test_select_fuse_prefers_depth_on_tpu_not_on_cpu(self):
        spec = laplace_jacobi(2)
        # memory-bound TPU cell: fusion wins until rim recompute crosses the
        # HBM saving (the model finds the crossover, not the deepest depth)
        assert select_fuse("pallas_fused", spec, (512, 512), 16,
                           "TPU v5 lite") > 1
        # compute-bound CPU cell (9-point box on a grid that tiles into row
        # blocks): fusing only adds rim recompute
        assert select_fuse("pallas", box(2), (4096, 4096), 16, "cpu") == 1
        # non-fusing backends and 3D kernels never fuse
        assert select_fuse("conv", spec, (64, 64), 16, "cpu") is None
        assert select_fuse("pallas", laplace_jacobi(3), (8, 16, 16), 16,
                           "TPU v5 lite") is None


class TestDeviceIdentity:
    """Profiles and tuned-table lookups key on ``jax.Device.device_kind``;
    a device with no profile is an error, never the CPU's rates."""

    def test_unknown_device_kind_raises(self):
        from repro.core.plan import device_profile
        spec = laplace_jacobi(2)
        with pytest.raises(ValueError, match="no device profile"):
            device_profile("TPU v99")
        with pytest.raises(ValueError, match="no device profile"):
            choose_backend(spec, TABLE1_GRID, iters=8, device_kind="tpu")
        with pytest.raises(ValueError, match="no device profile"):
            select_fuse("pallas_fused", spec, TABLE1_GRID, 16, "gpu")

    def test_current_device_is_read_from_jax(self):
        import jax
        from repro.core.plan import current_device_kind, device_profile
        assert current_device_kind() == jax.devices()[0].device_kind
        assert device_profile().kind == current_device_kind()

    def test_pallas_refused_where_its_block_overflows_vmem(self):
        # A geometry the TPU compiler would refuse is refused up front, with
        # a reason, and auto never picks it.
        from repro.core import backend_support
        spec = laplace_jacobi(2)
        wide = (64, 1 << 17)
        for b in ("pallas", "pallas_fused"):
            sup = backend_support(b, spec, grid_shape=wide)
            assert not sup and "VMEM" in sup.reason, sup
        name, _ = choose_backend(spec, wide, iters=8,
                                 device_kind="TPU v5 lite")
        assert name not in ("pallas", "pallas_fused")
        assert backend_support("pallas", spec, grid_shape=(8192, 8192))

    def test_v5e_peaks_are_the_published_ones(self):
        v5e = DEVICE_PROFILES["TPU v5 lite"]
        assert v5e.matmul_flops == 197e12 and v5e.mem_bw == 819e9
        assert v5e.pallas_native


class TestRecordedTimings:
    """Model vs the measured artifact this host last produced."""

    def test_measured_dense_conv_ratio_matches_model_ordering(self):
        solver = _load_bench()["solver"]
        keys = {k for k in solver}
        dense = next((solver[k] for k in keys if "dense/fp32" in k), None)
        conv = next((solver[k] for k in keys if "/conv/fp32" in k), None)
        if dense is None or conv is None:
            pytest.skip("artifact lacks dense/conv fp32 solver rows")
        measured_ratio = dense["s_per_iter"] / conv["s_per_iter"]
        assert measured_ratio > 10, measured_ratio

        spec = laplace_jacobi(2)
        cpu = DEVICE_PROFILES["cpu"]
        model_ratio = (
            estimate_seconds("dense", spec, TABLE1_GRID, TABLE1_ITERS, cpu)
            / estimate_seconds("conv", spec, TABLE1_GRID, TABLE1_ITERS, cpu))
        assert model_ratio > 10, model_ratio

    def test_recorded_auto_pick_matches_current_model(self):
        data = _load_bench()
        auto_keys = [k for k in data["us_per_call"] if "/auto=" in k]
        if not auto_keys:
            pytest.skip("artifact lacks an auto row")
        recorded = auto_keys[0].split("auto=")[1].split("/")[0]
        name, _ = choose_backend(laplace_jacobi(2), TABLE1_GRID,
                                 iters=TABLE1_ITERS, device_kind="cpu")
        assert recorded == name, (recorded, name)

    def test_solver_rows_have_stable_schema(self):
        data = _load_bench()
        assert data.get("schema", 0) >= 2
        for name, row in data["solver"].items():
            assert {"mode", "iters", "s_per_iter"} <= set(row), (name, row)
            assert row["iters"] >= 1
            assert row["s_per_iter"] > 0
            if row["mode"] == "converged":
                assert {"residual", "converged", "backend"} <= set(row), name
