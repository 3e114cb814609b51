"""Plan-cache tests (core/plan_cache.py) — the serving tier's artifact store.

Four layers of pinning:

  * keying — shapes sharing a power-of-two bucket share one compiled entry
    (hit), different buckets and scalar-weight variations of one tap-offset
    family behave as documented, and non-bucketable requests (dense/MATRIX,
    bc=None, array BCs, oversized pad ratios) degrade to exact entries that
    still cache;
  * exactness — a pad-to-bucket solve reproduces the unpadded solve on the
    same backend bit-for-bit: field, per-instance iteration counts,
    convergence flags; covered for bare, batched, variable-coefficient and
    source-carrying requests;
  * lifecycle — LRU eviction order, corrupt-entry evict-and-rebuild-once,
    stats counters (hits/misses/evictions/rebuilds/compile-seconds);
  * concurrency — racing threads on one key build it exactly once.

Probing is disabled (``probe=False``) except where the probe itself is under
test: these tests pin cache mechanics, not backend choice, and the roofline
path keeps them fast.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DirichletBC,
    PlanCache,
    StencilSpec,
    default_plan_cache,
    heterogeneous_jacobi,
    laplace_jacobi,
    set_default_plan_cache,
    solve,
)
from repro.core.autotune import TunedEntry, TunedTable, dtype_key, spec_family
from repro.core.boundary import BoundaryMode

GRID = (12, 12)
KW = dict(bc=0.5, rtol=1e-4, atol=0.0, check_every=10, max_iters=2000)


def _x0(grid, batch=None, seed=0, bc=0.5):
    """Random interior, shell at the Dirichlet value."""
    rng = np.random.default_rng(seed)
    shape = grid if batch is None else (batch, *grid)
    x = rng.standard_normal(shape).astype(np.float32)
    shell = np.ones(grid, np.float32)
    shell[tuple(slice(1, -1) for _ in grid)] = 0.0
    return x * (1.0 - shell) + bc * shell


def _cache(**kw):
    kw.setdefault("probe", False)
    return PlanCache(**kw)


class TestKeying:
    def test_same_bucket_hits(self):
        cache = _cache()
        s1 = cache.solver(laplace_jacobi(2), (12, 12), **KW)
        s2 = cache.solver(laplace_jacobi(2), (14, 10), **KW)
        assert s1.padded and s2.padded
        assert s1.bucket == s2.bucket == (16, 16)
        assert len(cache) == 1
        assert cache.stats.misses == 1 and cache.stats.hits == 1

    def test_different_bucket_misses(self):
        cache = _cache()
        cache.solver(laplace_jacobi(2), (12, 12), **KW)
        s = cache.solver(laplace_jacobi(2), (20, 20), **KW)
        assert s.bucket == (32, 32)
        assert len(cache) == 2 and cache.stats.misses == 2

    def test_scalar_weight_family_shares_entry(self):
        # Same tap offsets, different scalar weights -> one compiled loop
        # (weights stream through the fields operand).
        cache = _cache()
        cache.solver(laplace_jacobi(2), GRID, **KW)
        other = StencilSpec(
            taps={off: 0.2 for off, _ in laplace_jacobi(2).taps},
            name="fat_laplace")
        s = cache.solver(other, GRID, **KW)
        assert s.padded
        assert len(cache) == 1 and cache.stats.hits == 1

    def test_dirichlet_value_shares_entry(self):
        cache = _cache()
        cache.solver(laplace_jacobi(2), GRID, **KW)
        kw = dict(KW, bc=-3.0)
        cache.solver(laplace_jacobi(2), GRID, **kw)
        assert len(cache) == 1 and cache.stats.hits == 1

    def test_convergence_cfg_separates_entries(self):
        cache = _cache()
        cache.solver(laplace_jacobi(2), GRID, **KW)
        cache.solver(laplace_jacobi(2), GRID, **dict(KW, rtol=1e-6))
        assert len(cache) == 2 and cache.stats.misses == 2

    @pytest.mark.parametrize("kw", [
        dict(KW, bc=None),                         # raw application
        dict(KW, backend="dense", mode=BoundaryMode.MATRIX),
        dict(KW, bc=DirichletBC(np.full(GRID, 0.5, np.float32))),  # array BC
    ], ids=["bc-none", "dense-matrix", "array-bc"])
    def test_non_bucketable_degrades_to_exact(self, kw):
        cache = _cache()
        s = cache.solver(laplace_jacobi(2), GRID, **kw)
        assert not s.padded and s.bucket is None
        # still cached: the same request hits
        cache.solver(laplace_jacobi(2), GRID, **kw)
        assert cache.stats.hits == 1 and len(cache) == 1

    def test_oversized_pad_ratio_degrades_to_exact(self):
        # (17, 17) pads to (32, 32): ratio ~3.5 > 1.1 -> exact entry.
        cache = _cache(max_pad_ratio=1.1)
        s = cache.solver(laplace_jacobi(2), (17, 17), **KW)
        assert not s.padded
        cache.solver(laplace_jacobi(2), (17, 17), **KW)
        assert cache.stats.hits == 1


class TestExactness:
    """Padded executions must be indistinguishable from unpadded ones."""

    def _compare(self, spec, x0, x_atol=0.0, **kw):
        cache = _cache()
        cached = cache.solver(spec, x0.shape[-spec.ndim:], **kw)
        assert cached.padded, "exactness test must exercise the embedding"
        got = cached.solve(x0)
        want = solve(spec, x0, backend=cached.backend, **kw)
        if x_atol:
            np.testing.assert_allclose(
                np.asarray(got.x), np.asarray(want.x), atol=x_atol, rtol=0)
        else:
            assert np.array_equal(np.asarray(got.x), np.asarray(want.x))
        assert np.array_equal(np.asarray(got.iterations),
                              np.asarray(want.iterations))
        assert np.array_equal(np.asarray(got.converged),
                              np.asarray(want.converged))
        return got, want

    def test_bare_grid(self):
        got, want = self._compare(laplace_jacobi(2), _x0(GRID), **KW)
        assert got.converged and got.x.shape == GRID

    def test_batched_per_instance_iterations(self):
        # Instances converging at different times must freeze identically.
        x0 = np.stack([_x0(GRID, seed=s) for s in range(3)])
        x0[0] = 0.5  # already at the fixed point -> converges immediately
        got, want = self._compare(laplace_jacobi(2), x0, **KW)
        assert got.iterations[0] < got.iterations[1]

    def test_variable_coefficients(self):
        kappa = (1.0 + np.random.default_rng(3).random(GRID)
                 ).astype(np.float32)
        spec = heterogeneous_jacobi(kappa)
        # per-cell multiplies let XLA contract fma differently for the
        # bucket-shaped kernel: allow ulp-level drift on the field, but the
        # iteration counts and convergence decisions must still be identical
        got, _ = self._compare(spec, _x0(GRID, seed=1), x_atol=3e-7, **KW)
        assert got.converged

    def test_source_term(self):
        spec = laplace_jacobi(2)
        src = (np.random.default_rng(5).standard_normal(GRID) * 1e-2
               ).astype(np.float32)
        cache = _cache()
        cached = cache.solver(spec, GRID, **KW)
        got = cached.solve(_x0(GRID), source=src)
        want = solve(spec, _x0(GRID), backend=cached.backend, source=src, **KW)
        assert np.array_equal(np.asarray(got.x), np.asarray(want.x))
        assert got.iterations == want.iterations

    def test_one_shot_solve_entry_point(self):
        cache = _cache()
        got = cache.solve(laplace_jacobi(2), _x0(GRID), **KW)
        assert got.converged
        cache.solve(laplace_jacobi(2), _x0((14, 10), seed=2), **KW)
        assert cache.stats.hits == 1  # same bucket, no recompile


class TestLifecycle:
    def test_lru_eviction_order(self):
        cache = _cache(capacity=2)
        cache.solver(laplace_jacobi(2), (8, 8), **KW)      # bucket (8, 8)
        cache.solver(laplace_jacobi(2), (12, 12), **KW)    # bucket (16, 16)
        cache.solver(laplace_jacobi(2), (8, 8), **KW)      # touch (8, 8)
        cache.solver(laplace_jacobi(2), (20, 20), **KW)    # evicts (16, 16)
        assert len(cache) == 2 and cache.stats.evictions == 1
        buckets = [k[2] for k in cache.keys()]
        assert (8, 8) in buckets and (32, 32) in buckets
        # the evicted bucket misses again
        misses = cache.stats.misses
        cache.solver(laplace_jacobi(2), (12, 12), **KW)
        assert cache.stats.misses == misses + 1

    def test_corrupt_entry_rebuilds_once(self):
        cache = _cache()
        cached = cache.solver(laplace_jacobi(2), GRID, **KW)
        cache._entries[cached._entry.key].obj = None  # sabotage
        res = cached.solve(_x0(GRID))
        assert res.converged
        assert cache.stats.rebuilds == 1
        # the rebuilt entry serves subsequent calls without another rebuild
        assert cached.solve(_x0(GRID, seed=2)).converged
        assert cache.stats.rebuilds == 1

    def test_stats_shape(self):
        cache = _cache()
        cache.solver(laplace_jacobi(2), GRID, **KW)
        cache.solver(laplace_jacobi(2), GRID, **KW)
        d = cache.stats.as_dict()
        assert d["hits"] == 1 and d["misses"] == 1
        assert d["hit_rate"] == 0.5
        assert d["compile_seconds"] > 0.0

    def test_clear(self):
        cache = _cache()
        cache.solver(laplace_jacobi(2), GRID, **KW)
        cache.clear()
        assert len(cache) == 0

    def test_multigrid_entries_cache(self):
        cache = _cache()
        mg1 = cache.multigrid(laplace_jacobi(2), (17, 17), bc=0.0, rtol=1e-4)
        mg2 = cache.multigrid(laplace_jacobi(2), (17, 17), bc=0.0, rtol=1e-4)
        assert mg1 is mg2
        assert cache.stats.hits == 1
        res = mg1.solve(jnp.asarray(_x0((17, 17), bc=0.0)))
        assert res.converged

    def test_default_cache_swap(self):
        mine = _cache()
        old = set_default_plan_cache(mine)
        try:
            assert default_plan_cache() is mine
        finally:
            set_default_plan_cache(old)

    def test_probe_picks_a_capable_backend(self):
        # The measured-probe path must land on an operand-capable backend
        # and account its time.
        cache = PlanCache(probe=True, probe_iters=2)
        s = cache.solver(laplace_jacobi(2), (8, 8), **KW)
        assert s.backend in ("reference", "conv")
        assert cache.stats.probe_seconds > 0.0
        assert s.solve(_x0((8, 8))).converged


class TestBucketBackend:
    def test_measured_candidate_beats_faster_non_candidate(self):
        # Without the probe a bucket is routed by choose_schedule over its
        # candidates (reference, conv): a measured candidate wins even where
        # a backend the bucket cannot serve (dense) measured faster, and the
        # roofline (which picks reference here) is not asked.
        bucket = (16, 16)
        offsets = ((0, 1), (0, -1), (1, 0), (-1, 0))
        template = _cache()._template(offsets, bucket)

        def row(backend, us):
            return TunedEntry(device_kind="cpu", family=spec_family(template),
                              bucket=bucket, dtype=dtype_key(jnp.float32),
                              backend=backend, us_per_iter=us)

        def pick(tuned):
            return _cache(tuned=tuned)._bucket_backend(
                template, bucket, jnp.float32, None, "cpu")

        assert pick(None) == "reference"
        assert pick(TunedTable([row("dense", 0.5), row("conv", 5.0)])) == \
            "conv"


class TestConcurrency:
    def test_racing_threads_build_once(self, monkeypatch):
        cache = _cache()
        solvers, errors, builds = [], [], []

        orig = PlanCache._build_bucket

        def counting(self, *a, **kw):
            builds.append(threading.get_ident())
            return orig(self, *a, **kw)

        monkeypatch.setattr(PlanCache, "_build_bucket", counting)

        def work(seed):
            try:
                s = cache.solver(laplace_jacobi(2), GRID, **KW)
                solvers.append(s.solve(_x0(GRID, seed=seed)))
            except Exception as e:  # pragma: no cover - diagnostic
                errors.append(e)

        threads = [threading.Thread(target=work, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(solvers) == 6 and all(r.converged for r in solvers)
        assert len(cache) == 1
        assert len(builds) == 1  # the latch serialized construction
