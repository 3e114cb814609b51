"""The learned-stencil solver layer: the adjoint solve in the training stack.

Pins the ISSUE-9 integration surface: config registration, the ModelApi
contract (init/shapes/dims agree), training through the standard
``make_train_step`` + AdamW machinery (loss must drop on a recoverable
inverse problem), the sharding rules for grid-shaped params, and checkpoint
round-trips for trees holding ``WeightField`` leaves — including restore
under shardings.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.checkpoint.checkpoint import Checkpointer
from repro.core import WeightField, heterogeneous_jacobi, implicit_solve
from repro.models.model_zoo import build
from repro.models.solver_layer import SolverLayerConfig, solver_loss_fn
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import Sharder
from repro.train.train_step import (
    init_train_state,
    make_train_step,
    state_dims,
    state_shapes,
)

RNG = np.random.default_rng(20260809)


def _batch(cfg, n=4, seed=0):
    rng = np.random.default_rng(seed)
    true_spec = heterogeneous_jacobi(1.0 + 9.0 * rng.random(cfg.grid))
    src = jnp.asarray(rng.standard_normal((n, *cfg.grid)), jnp.float32)
    tgt = implicit_solve(true_spec, jnp.zeros_like(src),
                         fields=jnp.asarray(true_spec.field_stack()),
                         source=src, backend=cfg.backend, rtol=1e-6,
                         max_iters=2 * cfg.max_iters)
    return {"source": src, "target": tgt}


class TestConfigAndApi:
    def test_registered_config_builds(self):
        cfg = get_config("learned-stencil", smoke=True)
        assert cfg.family == "solver"
        api = build(cfg)
        assert api.cfg is cfg

    def test_rejects_non_differentiable_backend(self):
        with pytest.raises(ValueError, match="differentiable"):
            SolverLayerConfig(backend="pallas")

    def test_init_shapes_dims_agree(self):
        api = build(get_config("learned-stencil", smoke=True))
        params = api.init(jax.random.PRNGKey(0))
        shapes = api.shapes()
        dims = api.dims()
        assert jax.tree.structure(params) == jax.tree.structure(shapes)
        for key in ("taps", "bc"):
            assert params[key].shape == shapes[key].shape
            assert len(dims[key]) == params[key].ndim
        # taps start at the uniform-diffusion operator, bc at zero
        cfg = api.cfg
        np.testing.assert_array_equal(np.asarray(params["taps"]),
                                      cfg.init_weight)
        assert float(params["bc"]) == 0.0

    def test_forward_is_the_converged_solve(self):
        cfg = get_config("learned-stencil", smoke=True)
        api = build(cfg)
        params = api.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, n=2)
        out, aux = api.forward(params, batch)
        assert out.shape == batch["source"].shape
        want = implicit_solve(
            heterogeneous_jacobi(np.ones(cfg.grid)),
            jnp.zeros_like(batch["source"]), fields=params["taps"],
            source=batch["source"], bc_value=params["bc"],
            backend=cfg.backend, rtol=cfg.rtol, max_iters=cfg.max_iters)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=0)

    def test_token_entry_points_are_explicitly_absent(self):
        api = build(get_config("learned-stencil", smoke=True))
        with pytest.raises(NotImplementedError, match="steady states"):
            api.prefill(None, None, 0)
        with pytest.raises(NotImplementedError, match="steady states"):
            api.decode_step(None, None, None, 0)
        assert api.cache_shapes(None, 0) == {}
        assert api.cache_dims() == {}


class TestTraining:
    def test_loss_drops_through_the_standard_train_step(self):
        cfg = get_config("learned-stencil", smoke=True)
        api = build(cfg)
        batch = _batch(cfg, n=4)
        state = init_train_state(api, jax.random.PRNGKey(0))
        opt = AdamWConfig(lr=1e-2, warmup_steps=5, total_steps=25,
                          weight_decay=0.0, grad_clip=1.0)
        step = jax.jit(make_train_step(api, None, opt))
        first = float(solver_loss_fn(api, state["params"], batch)[0])
        for _ in range(25):
            state, metrics = step(state, batch)
        last = float(solver_loss_fn(api, state["params"], batch)[0])
        assert last < first / 2, (first, last)
        assert set(metrics) >= {"loss", "mse", "grad_norm", "lr"}

    def test_state_dims_cover_solver_state(self):
        api = build(get_config("learned-stencil", smoke=True))
        dims = state_dims(api)
        shapes = state_shapes(api)
        state = init_train_state(api, jax.random.PRNGKey(1))
        for k in ("params", "m", "v"):
            assert set(dims[k]) == set(state[k]) == {"taps", "bc"}
            for p in ("taps", "bc"):
                assert len(dims[k][p]) == state[k][p].ndim, (k, p)
        assert shapes["params"]["taps"].shape == state["params"]["taps"].shape
        assert shapes["step"].shape == ()


class _FakeMesh:
    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


class TestSharding:
    def test_taps_shard_rows_over_data(self):
        sh = Sharder(mesh=_FakeMesh(data=16, model=16), profile="tp")
        spec = sh.spec(("taps", "grid_row", "grid_col"), (4, 32, 32))
        assert spec == P(None, "data", None)

    def test_indivisible_grid_replicates(self):
        sh = Sharder(mesh=_FakeMesh(data=16, model=16), profile="tp")
        spec = sh.spec(("taps", "grid_row", "grid_col"), (4, 12, 14))
        assert spec == P(None, None, None)


class TestCheckpointWeightFields:
    def _tree(self):
        return {
            "spec_fields": WeightField(RNG.random((5, 6)).astype(np.float32)),
            "nested": {"wf": WeightField(RNG.random((3, 3)).astype(np.float32)),
                       "plain": np.arange(4, dtype=np.float32)},
            "scalar": np.float32(2.5),
        }

    def test_weight_field_round_trip_bitwise(self):
        tree = self._tree()
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(1, tree)
            step, restored = ck.restore_latest()
        assert step == 1
        assert isinstance(restored["spec_fields"], WeightField)
        assert isinstance(restored["nested"]["wf"], WeightField)
        np.testing.assert_array_equal(restored["spec_fields"].array,
                                      tree["spec_fields"].array)
        np.testing.assert_array_equal(restored["nested"]["wf"].array,
                                      tree["nested"]["wf"].array)
        np.testing.assert_array_equal(restored["nested"]["plain"],
                                      tree["nested"]["plain"])

    def test_weight_field_restore_under_shardings(self):
        # Restore with a shardings tree holding ONE sharding at the
        # WeightField's position: device_put broadcasts it over the wrapped
        # array instead of descending into the pytree node.
        tree = {"wf": WeightField(RNG.random((4, 4)).astype(np.float32)),
                "arr": np.ones((2, 2), np.float32)}
        dev = jax.devices()[0]
        shardings = {"wf": dev, "arr": dev}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(3, tree)
            _, restored = ck.restore_latest(shardings)
        assert isinstance(restored["wf"], WeightField)
        assert isinstance(restored["wf"].values, jax.Array)
        np.testing.assert_array_equal(np.asarray(restored["wf"].values),
                                      tree["wf"].array)

    def test_train_state_with_solver_params_round_trips(self):
        cfg = get_config("learned-stencil", smoke=True)
        api = build(cfg)
        state = init_train_state(api, jax.random.PRNGKey(0))
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(7, state)
            step, restored = ck.restore_latest()
        assert step == 7
        for k in ("params", "m", "v"):
            np.testing.assert_array_equal(
                np.asarray(restored[k]["taps"]),
                np.asarray(state[k]["taps"]), err_msg=k)
        assert int(restored["step"]) == int(state["step"])
