"""Blocked-matmul Pallas kernel for the paper's *dense-layer* encoding.

One Jacobi iteration = x(S,N) @ W(N,N) — the encoding is a plain GEMM, so
unlike the direct stencil this one *is* MXU work: (bm,bk)@(bk,bn) tiles,
fp32 VMEM accumulator, K-innermost grid with revisiting.  This kernel exists
to reproduce the paper's dense path faithfully at the kernel level and to
show on the roofline how its (2N−1)/7 redundancy dominates regardless of
how well the GEMM itself runs (EXPERIMENTS §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import round_up as _round_up


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bn", "interpret")
)
def dense_stencil_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bm: int = 128,
    bk: int = 512,
    bn: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """x: (S, N) @ w: (N, N) -> (S, N), fp32 accumulation in VMEM scratch."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    S, N = x.shape
    if w.shape != (N, N):
        raise ValueError(f"w must be ({N},{N}), got {w.shape}")
    bm = min(bm, _round_up(S, 8))
    bk = min(bk, _round_up(N, 128))
    bn = min(bn, _round_up(N, 128))
    Sp, Kp, Np = _round_up(S, bm), _round_up(N, bk), _round_up(N, bn)
    xp = jnp.pad(x, ((0, Sp - S), (0, Kp - N)))
    wp = jnp.pad(w, ((0, Kp - N), (0, Np - N)))

    out = pl.pallas_call(
        _kernel,
        grid=(Sp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Sp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wp)
    return out[:S, :N]
