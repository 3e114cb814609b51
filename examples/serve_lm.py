"""Serving example: batched prefill + greedy decode against the KV/SSM cache.

  PYTHONPATH=src python examples/serve_lm.py --arch qwen3-0.6b --tokens 16
  PYTHONPATH=src python examples/serve_lm.py --arch mamba2-370m --tokens 16

Runs a batch of synthetic prompts through prefill, then decodes N tokens,
timing per-token latency — the serve_step lowered by the decode_* dry-run
shapes, at CPU demo size.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.models.model_zoo import build

    cfg = get_config(args.arch, smoke=True)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)
    B, S = args.batch, args.prompt_len
    max_len = S + args.tokens + 1
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))}
    if cfg.family == "encdec":
        batch["enc_frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_len, cfg.d_model)), jnp.float32)
    if cfg.family == "vlm":
        nv = min(cfg.n_vision_tokens, S)
        batch["vision_embeds"] = jnp.asarray(
            rng.standard_normal((B, nv, cfg.d_model)), jnp.float32)

    t0 = time.perf_counter()
    last, cache = jax.block_until_ready(api.prefill(params, batch, max_len))
    t_prefill = time.perf_counter() - t0
    print(f"{args.arch}: prefill {B}x{S} in {t_prefill*1e3:.0f} ms "
          f"({B*S/t_prefill:.0f} tok/s)")

    logits = jnp.einsum("bd,vd->bv", last, params["lm_head"])
    token = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [token]
    step = jax.jit(lambda p, t, c, k: api.decode_step(p, t, c, k),
                   static_argnums=3)
    t0 = time.perf_counter()
    for i in range(args.tokens):
        logits, cache = step(params, token, cache, S + i)
        token = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(token)
    jax.block_until_ready(token)
    dt = (time.perf_counter() - t0) / args.tokens
    print(f"decode: {dt*1e3:.1f} ms/token ({B/dt:.0f} tok/s batched)")
    print("generated token ids (seq 0):",
          [int(t[0]) for t in out][: args.tokens + 1])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
