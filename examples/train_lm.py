"""End-to-end LM training driver: data pipeline -> sharded train step ->
fault-tolerant runtime with checkpointing.

  PYTHONPATH=src python examples/train_lm.py                 # CPU demo (~8M params, 200 steps)
  PYTHONPATH=src python examples/train_lm.py --full          # ~100M config (needs accelerator time)
  PYTHONPATH=src python examples/train_lm.py --arch mamba2-370m  # any zoo arch (smoke size)

Demonstrates: loss descending on the synthetic stream, checkpoint/restart
(kill it mid-run and re-invoke — it resumes), straggler flagging.
"""
import argparse
import dataclasses
import sys

sys.path.insert(0, "src")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--full", action="store_true",
                    help="~100M-param config instead of the CPU demo size")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--checkpoint-dir", default="artifacts/train_lm_ckpt")
    args = ap.parse_args()

    from repro.launch.train import main as train_main

    argv = ["--arch", args.arch, "--smoke",
            "--steps", str(args.steps),
            "--global-batch", str(args.global_batch),
            "--seq-len", str(args.seq_len),
            "--checkpoint-dir", args.checkpoint_dir,
            "--checkpoint-every", "50",
            "--log-every", "10"]
    if args.full:
        # ~100M: override the smoke config in-place via a registered variant
        import repro.configs.base as B
        from repro.configs import get_config
        base = get_config(args.arch, smoke=True)
        cfg100 = dataclasses.replace(
            base, arch=base.arch + "-100m", n_layers=12, d_model=768,
            n_heads=12, n_kv_heads=4, head_dim=64, d_ff=2048,
            vocab_size=32768)
        B.register(base.arch + "-100m", lambda: cfg100, lambda: cfg100)
        argv = ["--arch", base.arch + "-100m"] + argv[2:]
    return train_main(argv)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
