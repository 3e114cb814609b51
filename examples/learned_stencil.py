"""Learn a stencil from steady states — the adjoint solve as a layer.

Inverse problem: a hidden heterogeneous conductivity field ``kappa`` defines
a diffusion operator; we observe (source, steady-state) pairs produced by
solving it, and recover the operator by gradient descent *through the
solver*.  The forward pass is ``implicit_solve`` run to convergence; the
backward pass is one adjoint solve with the transposed stencil (O(1) memory
in iteration count — see src/repro/core/adjoint.py), so the whole thing
trains under the repo's standard ``make_train_step`` + AdamW stack, with a
checkpoint round-trip mid-run to prove solver state restores exactly.

  PYTHONPATH=src python examples/learned_stencil.py            # full run
  PYTHONPATH=src python examples/learned_stencil.py --smoke \
      --steps 20 --assert-decreasing                           # CI smoke
"""
import argparse
import sys
import tempfile

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np


def make_dataset(cfg, n_batches, batch, seed=0):
    """(source, target) pairs from a hidden ground-truth operator."""
    from repro.core import heterogeneous_jacobi, implicit_solve

    rng = np.random.default_rng(seed)
    kappa = 1.0 + 9.0 * rng.random(cfg.grid)
    true_spec = heterogeneous_jacobi(kappa, name="hidden-kappa")
    true_fields = jnp.asarray(true_spec.field_stack())
    data = []
    for _ in range(n_batches):
        src = jnp.asarray(rng.standard_normal((batch, *cfg.grid)), jnp.float32)
        tgt = implicit_solve(
            true_spec, jnp.zeros_like(src), fields=true_fields, source=src,
            backend=cfg.backend, rtol=1e-6, max_iters=2 * cfg.max_iters)
        data.append({"source": src, "target": tgt})
    return data, true_fields


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid / few iterations (CPU CI)")
    ap.add_argument("--steps", type=int, default=250)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--assert-decreasing", action="store_true",
                    help="exit nonzero unless loss drops >= 10x")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.checkpoint.checkpoint import Checkpointer
    from repro.models.model_zoo import build
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import init_train_state, make_train_step

    cfg = get_config("learned-stencil", smoke=args.smoke)
    api = build(cfg)
    print(f"== learned-stencil on {cfg.grid}, backend={cfg.backend}, "
          f"{args.steps} steps ==")

    # Full-batch training: the inverse problem is deterministic, and batch
    # rotation only adds optimizer churn that short runs cannot average out.
    data, true_fields = make_dataset(cfg, n_batches=1, batch=args.batch)
    state = init_train_state(api, jax.random.PRNGKey(0))
    opt = AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps,
                      weight_decay=0.0, grad_clip=1.0)
    step = jax.jit(make_train_step(api, None, opt))

    # The 10x criterion is judged on one fixed batch — per-step train losses
    # come from rotating batches and are not comparable to each other.
    from repro.models.solver_layer import solver_loss_fn
    eval_loss = jax.jit(
        lambda params: solver_loss_fn(api, params, data[0])[0])
    first = float(eval_loss(state["params"]))
    ckpt_at = max(1, args.steps // 2)
    with tempfile.TemporaryDirectory() as ckdir:
        ck = Checkpointer(ckdir, keep=2)
        for i in range(args.steps):
            state, metrics = step(state, data[i % len(data)])
            loss = float(metrics["loss"])
            if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
                print(f"step {i:4d}  loss {loss:.3e}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"|g| {float(metrics['grad_norm']):.2e}")
            if i + 1 == ckpt_at:
                # Round-trip the full train state through a checkpoint and
                # keep training from the restored copy — the restored solve
                # must continue bit-for-bit.
                ck.save(i + 1, state)
                _, restored = ck.restore_latest()
                before = step(state, data[0])[1]["loss"]
                after = step(restored, data[0])[1]["loss"]
                assert float(before) == float(after), (before, after)
                state = restored
                print(f"step {i+1:4d}  checkpoint round-trip OK "
                      f"(loss identical: {float(after):.3e})")

    last = float(eval_loss(state["params"]))
    taps = state["params"]["taps"]
    tap_err = float(jnp.abs(taps - true_fields).mean())
    print(f"eval loss {last:.3e} ({first / max(last, 1e-30):.0f}x down "
          f"from {first:.3e}); mean |taps - true| = {tap_err:.3f}")
    if args.assert_decreasing and not last <= first / 10.0:
        print("FAIL: loss did not decrease 10x", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
