"""Autotuner tier: the measured tuned-table dispatch contract.

Pins down (a) measured entries beating the roofline in ``choose_backend`` /
``make_plan`` / ``select_fuse``, (b) the explicit roofline fallback when no
entry applies, (c) corrupt / stale tables degrading with a warning instead
of crashing dispatch, (d) interpret-mode measurements never winning a cell,
(e) the extended fusion geometry (rim="resident") staying exact, and (f) the
hillclimb harness no longer clobbering a caller's XLA_FLAGS at import time.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DirichletBC,
    choose_backend,
    laplace_jacobi,
    make_plan,
    stencil_apply,
)
from repro.core.autotune import (
    SCHEMA_VERSION,
    TableError,
    TunedEntry,
    TunedTable,
    bucket_distance,
    dtype_key,
    set_default_tuned_table,
    shape_bucket,
    spec_family,
    validate_table,
)
from repro.core.reference import jacobi_reference
from repro.core.solver import select_fuse

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SPEC = laplace_jacobi(2)
GRID = (64, 64)
FAM = spec_family(SPEC)
F32 = dtype_key(jnp.float32)


def entry(backend, us, *, fuse=1, block_h=None, rim=None, interpreted=False,
          device_kind="cpu", bucket=GRID, family=FAM, dtype=F32):
    return TunedEntry(device_kind=device_kind, family=family, bucket=bucket,
                      dtype=dtype, backend=backend, us_per_iter=us, fuse=fuse,
                      block_h=block_h, rim=rim, interpreted=interpreted)


@pytest.fixture(autouse=True)
def _isolate_default_table(monkeypatch, tmp_path):
    """Point the process-wide default table at a nonexistent file so these
    tests never read (or are polluted by) the committed artifact."""
    monkeypatch.setenv("REPRO_TUNED_TABLE", str(tmp_path / "absent.json"))
    set_default_tuned_table(None)
    yield
    set_default_tuned_table(None)


# ---------------------------------------------------------------------------
# Cell keys
# ---------------------------------------------------------------------------

class TestCellKeys:
    def test_family_is_structural(self):
        assert FAM == "2d/r1/t4"
        assert spec_family(laplace_jacobi(3)) == "3d/r1/t6"
        from repro.core import heterogeneous_jacobi
        k = np.ones(GRID, np.float32)
        assert spec_family(heterogeneous_jacobi(k)).endswith("/var")

    def test_shape_bucket_rounds_up_to_pow2(self):
        assert shape_bucket((60, 64)) == (64, 64)
        assert shape_bucket((65, 1)) == (128, 1)

    def test_bucket_distance(self):
        assert bucket_distance((64, 64), (64, 64)) == 0.0
        assert bucket_distance((64, 64), (128, 64)) == 1.0
        assert bucket_distance((64, 64), (64, 64, 64)) == float("inf")


# ---------------------------------------------------------------------------
# Measured entries beat the roofline
# ---------------------------------------------------------------------------

class TestMeasuredPreference:
    def test_choose_backend_prefers_measured_entry_over_roofline(self):
        # The roofline on CPU picks conv for this cell; a measured table
        # claiming a compiled pallas_fused schedule is faster must override.
        roof_name, _ = choose_backend(SPEC, GRID, iters=100,
                                      device_kind="cpu", tuned=None)
        assert roof_name == "conv"
        table = TunedTable((entry("conv", 100.0),
                            entry("pallas_fused", 5.0, fuse=8, block_h=64)))
        name, costs = choose_backend(SPEC, GRID, iters=100,
                                     device_kind="cpu", tuned=table)
        assert name == "pallas_fused"
        # the returned cost table is the measured one, argmin included
        assert costs[name] == min(costs.values())
        assert costs["pallas_fused"] == pytest.approx(5e-6 * 100)

    def test_make_plan_inherits_tuned_schedule(self):
        table = TunedTable((entry("conv", 100.0),
                            entry("pallas_fused", 5.0, fuse=8, block_h=64,
                                  rim="trapezoid")))
        plan = make_plan(SPEC, GRID, backend="auto", bc=1.0, iters=16,
                         device_kind="cpu", tuned=table)
        assert plan.source == "tuned"
        assert plan.backend == "pallas_fused"
        assert plan.fuse == 8 and plan.rim == "trapezoid"

    def test_tuned_fuse_not_inherited_when_it_does_not_divide(self):
        table = TunedTable((entry("pallas_fused", 5.0, fuse=8),))
        plan = make_plan(SPEC, GRID, backend="auto", bc=1.0, iters=12,
                         device_kind="cpu", tuned=table)
        assert plan.backend == "pallas_fused"
        assert 12 % plan.fuse == 0  # fell back to a legal depth

    def test_solver_plan_reports_choice_source(self):
        from repro.core import Solver
        table = TunedTable((entry("conv", 10.0),))
        s = Solver(SPEC, GRID, bc=1.0, rtol=None, atol=None, max_iters=4,
                   device_kind="cpu", tuned=table)
        assert s.backend == "conv" and s.plan.source == "tuned"
        s = Solver(SPEC, GRID, bc=1.0, rtol=None, atol=None, max_iters=4,
                   device_kind="cpu", tuned=None)
        assert s.plan.source == "roofline"
        s = Solver(SPEC, GRID, backend="conv", bc=1.0, rtol=None, atol=None,
                   max_iters=4, device_kind="cpu", tuned=None)
        assert s.plan.source == "explicit"

    def test_select_fuse_takes_measured_depth_with_clamping(self):
        table = TunedTable((entry("pallas_fused", 5.0, fuse=8),))
        assert select_fuse("pallas_fused", SPEC, GRID, 16, "cpu",
                           tuned=table) == 8
        # clamped down to a divisor of check_every
        assert select_fuse("pallas_fused", SPEC, GRID, 20, "cpu",
                           tuned=table) == 5
        # non-fusing backends stay None regardless of the table
        assert select_fuse("conv", SPEC, GRID, 16, "cpu", tuned=table) is None

    def test_tuned_plan_still_matches_oracle(self):
        table = TunedTable((entry("pallas_fused", 5.0, fuse=4,
                                  rim="resident"),))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                        jnp.float32)
        got = stencil_apply(SPEC, x, backend="auto", bc=1.0, iters=4,
                            device_kind="cpu", tuned=table)
        want = jacobi_reference(x, SPEC, DirichletBC(1.0), 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# Explicit roofline fallback
# ---------------------------------------------------------------------------

class TestRooflineFallback:
    def test_empty_table_matches_disabled_table(self):
        a = choose_backend(SPEC, GRID, iters=100, device_kind="cpu",
                           tuned=TunedTable())
        b = choose_backend(SPEC, GRID, iters=100, device_kind="cpu",
                           tuned=None)
        assert a == b

    def test_far_bucket_falls_back_to_roofline(self):
        # Entry recorded at 64x64; a 4096x4096 query is 12 doublings away —
        # outside the default max_distance — so the roofline decides.
        table = TunedTable((entry("pallas_fused", 5.0, fuse=8),))
        name, _ = choose_backend(SPEC, (4096, 4096), iters=100,
                                 device_kind="cpu", tuned=table)
        roof, _ = choose_backend(SPEC, (4096, 4096), iters=100,
                                 device_kind="cpu", tuned=None)
        assert name == roof == "conv"

    def test_near_bucket_transfers(self):
        table = TunedTable((entry("pallas_fused", 5.0, fuse=8),))
        name, _ = choose_backend(SPEC, (60, 60), iters=8, device_kind="cpu",
                                 tuned=table)  # same bucket
        assert name == "pallas_fused"
        name, _ = choose_backend(SPEC, (100, 100), iters=8,
                                 device_kind="cpu", tuned=table)  # 1 away
        assert name == "pallas_fused"

    def test_wrong_family_or_dtype_misses(self):
        # The entry is keyed (cpu, 2d/r1/t4, fp32): a 3D query or a bf16
        # query must behave exactly as if the table were disabled.
        table = TunedTable((entry("pallas_fused", 5.0),))
        name, _ = choose_backend(laplace_jacobi(3), (8, 16, 16), iters=8,
                                 device_kind="cpu", tuned=table)
        assert name == choose_backend(laplace_jacobi(3), (8, 16, 16),
                                      iters=8, device_kind="cpu",
                                      tuned=None)[0]
        name, _ = choose_backend(SPEC, GRID, iters=8, device_kind="cpu",
                                 dtype=jnp.bfloat16, tuned=table)
        assert name == "conv"


# ---------------------------------------------------------------------------
# Interpret-mode entries never win
# ---------------------------------------------------------------------------

class TestInterpretedExclusion:
    def test_interpreted_entry_cannot_win_cell(self):
        table = TunedTable((entry("pallas", 1.0, interpreted=True),
                            entry("conv", 50.0)))
        name, costs = choose_backend(SPEC, GRID, iters=8, device_kind="cpu",
                                     tuned=table)
        assert name == "conv"
        assert "pallas" not in costs

    def test_only_interpreted_entries_fall_back_to_roofline(self):
        table = TunedTable((entry("pallas", 1.0, interpreted=True),
                            entry("pallas_fused", 1.0, interpreted=True)))
        name, _ = choose_backend(SPEC, GRID, iters=100, device_kind="cpu",
                                 tuned=table)
        assert name == "conv"  # roofline fallback, not interpreted pallas

    def test_table_lookup_skips_interpreted(self):
        table = TunedTable((entry("pallas", 1.0, interpreted=True),
                            entry("conv", 50.0)))
        best = table.lookup("cpu", FAM, GRID, F32)
        assert best is not None and best.backend == "conv"


# ---------------------------------------------------------------------------
# Corrupt / stale artifacts degrade, never crash
# ---------------------------------------------------------------------------

class TestTableRobustness:
    def test_corrupt_json_warns_and_degrades(self, tmp_path):
        p = tmp_path / "TUNED_stencil.json"
        p.write_text("{not json", encoding="utf-8")
        with pytest.warns(UserWarning, match="ignoring tuned table"):
            table = TunedTable.load(str(p))
        assert len(table) == 0
        # dispatch through the bad table still works (roofline fallback)
        name, _ = choose_backend(SPEC, GRID, iters=100, device_kind="cpu",
                                 tuned=table)
        assert name == "conv"

    def test_stale_schema_warns_and_degrades(self, tmp_path):
        p = tmp_path / "TUNED_stencil.json"
        p.write_text(json.dumps({"schema": SCHEMA_VERSION + 1,
                                 "entries": []}), encoding="utf-8")
        with pytest.warns(UserWarning, match="stale or future"):
            table = TunedTable.load(str(p))
        assert len(table) == 0

    def test_missing_file_is_silently_empty(self, tmp_path):
        table = TunedTable.load(str(tmp_path / "nope.json"))
        assert len(table) == 0

    def test_default_table_env_override_survives_corruption(self, monkeypatch,
                                                            tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("[]", encoding="utf-8")
        monkeypatch.setenv("REPRO_TUNED_TABLE", str(p))
        set_default_tuned_table(None)
        with pytest.warns(UserWarning):
            plan = make_plan(SPEC, GRID, backend="auto", bc=1.0, iters=4,
                             device_kind="cpu")  # tuned="default"
        assert plan.source == "roofline"
        assert plan.backend == "conv"

    def test_strict_parse_raises(self):
        with pytest.raises(TableError):
            TunedTable.parse({"schema": 999, "entries": []})
        with pytest.raises(TableError):
            TunedTable.parse({"schema": SCHEMA_VERSION,
                              "entries": [{"bogus": 1}]})

    def test_roundtrip(self, tmp_path):
        table = TunedTable((entry("conv", 50.0),
                            entry("pallas_fused", 5.0, fuse=8, block_h=128,
                                  rim="trapezoid")))
        p = tmp_path / "t.json"
        table.save(str(p))
        back = TunedTable.load(str(p))
        assert sorted(e.backend for e in back.entries) == \
            ["conv", "pallas_fused"]
        assert back.lookup("cpu", FAM, GRID, F32).fuse == 8


# ---------------------------------------------------------------------------
# Table validation (scripts/ci.sh --tune-check)
# ---------------------------------------------------------------------------

class TestValidation:
    def test_valid_table_passes(self):
        table = TunedTable((entry("conv", 50.0),))
        assert validate_table(table.to_json()) == []

    def test_unknown_backend_fails(self):
        data = TunedTable((entry("conv", 50.0),)).to_json()
        data["entries"][0]["backend"] = "tensorcore9000"
        assert any("unknown backend" in e for e in validate_table(data))

    def test_illegal_support_cell_fails(self):
        # conv has no 1D encoding: a 1d family conv entry must fail CI.
        data = TunedTable((entry("conv", 50.0, family="1d/r1/t2",
                                 bucket=(64,)),)).to_json()
        assert any("legal backend_support" in e for e in validate_table(data))

    def test_wrong_schema_fails(self):
        assert validate_table({"schema": 99, "entries": []})

    def test_committed_table_validates(self):
        path = os.path.join(REPO, "TUNED_stencil.json")
        if not os.path.exists(path):
            pytest.skip("no committed TUNED_stencil.json")
        with open(path) as f:
            data = json.load(f)
        assert validate_table(data) == []
        assert len(data["entries"]) >= 1


# ---------------------------------------------------------------------------
# Extended fusion geometry
# ---------------------------------------------------------------------------

class TestMeshKeyedEntries:
    """Halo schedules are tuned per mesh shape and must stay mesh-exact."""

    def test_mesh_roundtrips_and_is_omitted_when_absent(self, tmp_path):
        t = TunedTable()
        t.add(entry("conv", 5.0))
        t.add(TunedEntry(device_kind="cpu", family=FAM, bucket=GRID,
                         dtype=F32, backend="halo", us_per_iter=3.0,
                         fuse=4, mesh=(2, 4)))
        p = tmp_path / "t.json"
        t.save(str(p))
        raw = json.loads(p.read_text())
        by_backend = {e["backend"]: e for e in raw["entries"]}
        assert "mesh" not in by_backend["conv"]
        assert by_backend["halo"]["mesh"] == [2, 4]
        t2 = TunedTable.load(str(p))
        halo = next(e for e in t2.entries if e.backend == "halo")
        assert halo.mesh == (2, 4)

    def test_lookup_filters_on_mesh_shape(self):
        t = TunedTable()
        t.add(TunedEntry(device_kind="cpu", family=FAM, bucket=GRID,
                         dtype=F32, backend="halo", us_per_iter=3.0,
                         fuse=4, mesh=(2, 4)))
        t.add(entry("conv", 5.0))
        # no mesh given: the halo entry is invisible, conv still applies
        assert t.lookup("cpu", FAM, GRID, F32).backend == "conv"
        # matching mesh: the (faster) halo entry wins
        hit = t.lookup("cpu", FAM, GRID, F32, mesh_shape=(2, 4))
        assert hit.backend == "halo" and hit.fuse == 4
        # a different mesh shape must not inherit the timing
        assert t.lookup("cpu", FAM, GRID, F32,
                        mesh_shape=(2, 2)).backend == "conv"

    def test_select_fuse_takes_mesh_matched_halo_depth(self):
        t = TunedTable()
        t.add(TunedEntry(device_kind="cpu", family=FAM, bucket=GRID,
                         dtype=F32, backend="halo", us_per_iter=3.0,
                         fuse=8, mesh=(2, 4)))
        f = select_fuse("halo", SPEC, GRID, 16, "cpu", tuned=t, mesh=(2, 4))
        assert f == 8
        # clamped to a divisor of check_every
        assert select_fuse("halo", SPEC, GRID, 12, "cpu", tuned=t,
                           mesh=(2, 4)) == 6
        # and to the depth the local tile can host: (8, 8) over (2, 4)
        # leaves 4x2 tiles, so the measured 8 collapses to 2
        assert select_fuse("halo", SPEC, (8, 8), 16, "cpu", tuned=t,
                           mesh=(2, 4)) == 2

    def test_halo_schedule_candidates_respect_tile_and_chunk(self):
        from repro.core.autotune import halo_schedule_candidates
        cands = halo_schedule_candidates(SPEC, (64, 64), (2, 4), 16)
        assert [c.fuse for c in cands] == [1, 2, 4, 8]
        assert all(c.backend == "halo" for c in cands)
        # 12-iteration chunks drop the non-dividing depths
        assert [c.fuse for c in
                halo_schedule_candidates(SPEC, (64, 64), (2, 4), 12)] == [1, 2, 4]
        # tiny tiles clamp the sweep; non-tiling grids yield nothing
        assert [c.fuse for c in
                halo_schedule_candidates(SPEC, (8, 8), (2, 4), 16)] == [1, 2]
        assert halo_schedule_candidates(SPEC, (9, 9), (2, 4), 16) == []

    def test_validation_enforces_mesh_discipline(self):
        t = TunedTable()
        t.add(TunedEntry(device_kind="cpu", family=FAM, bucket=GRID,
                         dtype=F32, backend="halo", us_per_iter=3.0,
                         fuse=4, mesh=(2, 4)))
        assert validate_table(t.to_json()) == []
        # halo without a mesh is an invalid artifact
        bare = TunedTable()
        bare.add(entry("halo", 3.0))
        errs = validate_table(bare.to_json())
        assert errs and "mesh" in errs[0]
        # mesh on a single-device backend is equally invalid
        wrong = TunedTable()
        wrong.add(TunedEntry(device_kind="cpu", family=FAM, bucket=GRID,
                             dtype=F32, backend="conv", us_per_iter=3.0,
                             mesh=(2, 2)))
        errs = validate_table(wrong.to_json())
        assert errs and "halo-only" in errs[0]


class TestResidentRim:
    def test_resident_matches_reference_deep_fuse(self):
        # Depths the trapezoid geometry rejects outright on a 33x57 grid.
        from repro.kernels import jacobi2d
        from repro.kernels.ref import jacobi2d_ref
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((2, 33, 57)), jnp.float32)
        got = jacobi2d(x, SPEC, bc_value=1.0, iterations=32, fuse=32,
                       rim="resident")
        want = jacobi2d_ref(x, SPEC, 1.0, 32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_resident_rejects_oversized_grids(self):
        from repro.core.plan import _pallas_fits, device_profile
        v5e = device_profile("TPU v5 lite")
        assert _pallas_fits(SPEC, (64, 64), 32, v5e, rim="resident")
        assert not _pallas_fits(SPEC, (4096, 4096), 32, v5e, rim="resident")
        with pytest.raises(ValueError, match="scoped VMEM"):
            make_plan(SPEC, (4096, 4096), backend="pallas_fused", bc=1.0,
                      iters=32, fuse=32, rim="resident",
                      device_kind="TPU v5 lite")

    def test_unknown_rim_raises(self):
        with pytest.raises(ValueError, match="rim"):
            from repro.kernels.tiling import fused_block_geometry
            fused_block_geometry(64, 64, 4, 1, rim="mystery")


# ---------------------------------------------------------------------------
# hillclimb harness regressions
# ---------------------------------------------------------------------------

class TestHillclimbEnv:
    def test_import_does_not_clobber_xla_flags(self):
        code = (
            "import os\n"
            "os.environ['XLA_FLAGS'] = '--xla_gpu_autotune_level=0'\n"
            "import benchmarks.hillclimb\n"
            "assert os.environ['XLA_FLAGS'] == "
            "'--xla_gpu_autotune_level=0', os.environ['XLA_FLAGS']\n"
            "print('CLEAN')\n"
        )
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env={**os.environ,
                                "PYTHONPATH": os.path.join(REPO, "src")},
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "CLEAN" in r.stdout

    def test_force_host_devices_appends(self, monkeypatch):
        from benchmarks.hillclimb import _force_host_devices
        monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
        _force_host_devices(8)
        assert os.environ["XLA_FLAGS"] == \
            "--xla_foo=1 --xla_force_host_platform_device_count=8"
        # idempotent: an existing device-count flag is left alone
        _force_host_devices(16)
        assert "device_count=8" in os.environ["XLA_FLAGS"]

    def test_roofline_constants_come_from_device_profiles(self):
        import inspect
        from benchmarks import hillclimb
        src = inspect.getsource(hillclimb.run)
        for const in ("197e12", "819e9", "50e9"):
            assert const not in src
        assert "DEVICE_PROFILES" in src
