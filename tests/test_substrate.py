"""Substrate tests: optimizer, data pipeline determinism, checkpointing
(incl. elastic restore), fault-tolerant runtime restart-equivalence."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import Checkpointer
from repro.data.synthetic import DataConfig, token_batch
from repro.optim.adamw import AdamWConfig, apply_update, init_state, schedule


class TestAdamW:
    def test_quadratic_converges(self):
        target = jnp.asarray([1.0, -2.0, 3.0])
        state = init_state({"w": jnp.zeros(3)})
        cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=10,
                          total_steps=300)
        for _ in range(300):
            g = {"w": 2 * (state["params"]["w"] - target)}
            state, m = apply_update(state, g, cfg)
        np.testing.assert_allclose(state["params"]["w"], target, atol=1e-2)

    def test_grad_clip(self):
        state = init_state({"w": jnp.zeros(2)})
        cfg = AdamWConfig(lr=1e-3, grad_clip=1.0)
        _, m = apply_update(state, {"w": jnp.asarray([1e6, 0.0])}, cfg)
        assert float(m["grad_norm"]) > 1e5  # reported pre-clip

    def test_schedule_shape(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
        assert float(schedule(cfg, jnp.asarray(5))) == pytest.approx(0.5)
        assert float(schedule(cfg, jnp.asarray(100))) == pytest.approx(0.1, abs=1e-3)


class TestData:
    def test_deterministic_across_host_counts(self):
        cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=8)
        full = token_batch(cfg, step=3, n_hosts=1, host_id=0)
        parts = [token_batch(cfg, step=3, n_hosts=4, host_id=h)["tokens"]
                 for h in range(4)]
        np.testing.assert_array_equal(np.concatenate(parts), full["tokens"])

    def test_labels_are_shifted_tokens(self):
        cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2)
        b = token_batch(cfg, 0)
        # same underlying stream: labels[t] == tokens[t+1]
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        tree = {"a": {"b": jnp.arange(5, dtype=jnp.float32)},
                "step": jnp.asarray(7)}
        ck.save(7, tree)
        step, back = ck.restore_latest()
        assert step == 7
        np.testing.assert_array_equal(back["a"]["b"], tree["a"]["b"])

    def test_keep_n(self, tmp_path):
        ck = Checkpointer(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            ck.save(s, {"x": jnp.zeros(1)})
        files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
        assert len(files) == 2
        assert ck.latest_step() == 4

    def test_async_save(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, {"x": jnp.ones(3)}, blocking=False)
        ck.wait()
        assert ck.latest_step() == 1

    def test_elastic_restore_on_different_mesh(self, tmp_path):
        # save unsharded, restore under an explicit (trivial) sharding -> works
        from jax.sharding import NamedSharding, PartitionSpec as P
        ck = Checkpointer(str(tmp_path))
        tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
        ck.save(2, tree)
        mesh = jax.make_mesh((1,), ("data",))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        _, back = ck.restore_latest(sh)
        np.testing.assert_array_equal(back["w"], tree["w"])
        assert back["w"].sharding == sh["w"]


class TestFTRuntime:
    def _setup(self, tmp_path, fail_at=None):
        from repro.runtime.ft import FTConfig, run_training

        def train_step(state, batch):
            w = state["w"] - 0.1 * batch
            return {"w": w, "step": state["step"] + 1}, {"loss": jnp.sum(w * w)}

        def init():
            return {"w": jnp.ones(4), "step": jnp.asarray(0)}

        def batch_for(step):
            return jnp.full(4, float(step % 3))

        ft = FTConfig(checkpoint_dir=str(tmp_path), checkpoint_every=3,
                      async_save=False, fail_at_step=fail_at)
        return train_step, init, batch_for, ft, run_training

    def test_restart_equivalence(self, tmp_path):
        from repro.runtime.ft import InjectedFailure
        step, init, batch_for, ft, run = self._setup(tmp_path, fail_at=7)
        with pytest.raises(InjectedFailure):
            run(step, init, batch_for, 10, ft)
        ft2 = self._setup(tmp_path)[3]
        state, stats = run(step, init, batch_for, 10, ft2)

        # uninterrupted reference
        ref_state, _ = run(step, init, batch_for, 10,
                           self._setup(str(tmp_path) + "_ref")[3])
        np.testing.assert_allclose(state["w"], ref_state["w"], rtol=1e-6)

    def test_straggler_flagging(self, tmp_path):
        import time
        from repro.runtime.ft import FTConfig, run_training

        calls = {"n": 0}

        def train_step(state, batch):
            calls["n"] += 1
            if calls["n"] == 8:
                time.sleep(0.25)
            return state, {"loss": jnp.zeros(())}

        ft = FTConfig(checkpoint_dir=str(tmp_path), checkpoint_every=100,
                      async_save=False, straggler_factor=3.0)
        _, stats = run_training(train_step, lambda: {"w": jnp.zeros(1)},
                                lambda s: jnp.zeros(1), 10, ft)
        assert any(s.is_straggler for s in stats)


class TestCompileCache:
    """JAX's persistent compile cache has one fixed home: the environment's
    ``JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``."""

    def test_env_var_wins(self, monkeypatch, tmp_path):
        from repro.compile_cache import compile_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache_dir() == str(tmp_path)

    def test_fixed_path_inside_the_checkout(self, monkeypatch):
        from repro.compile_cache import compile_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache_dir() == os.path.join(repo, ".jax_cache")
        # the same path every time: nothing derived from a pid or a clock
        assert compile_cache_dir() == compile_cache_dir()

    def test_enable_sets_nothing_when_the_env_names_it(self, monkeypatch,
                                                       tmp_path):
        from repro.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
