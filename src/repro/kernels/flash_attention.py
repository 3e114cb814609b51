"""Flash attention (forward) Pallas TPU kernel — online-softmax attention
whose scores live only in VMEM (arXiv:2205.14135, re-tiled for the MXU).

Purpose in this framework (§Perf C): the XLA attention path materializes
(B, H, Sq, Skv) fp32 score buffers in HBM several times per layer — the
single largest memory-term contributor in every dry-run cell.  This kernel
streams K/V blocks through VMEM with a running (m, l, acc) online softmax,
so HBM traffic collapses to q/k/v/o (≈ (2S·hd·3 + S·hd) bytes vs ≈ S²·4·k).

Grid: (batch, q_heads, Sq/bq, Skv/bk) — kv innermost ("arbitrary"), with
fp32 accumulators in VMEM scratch, causal block skipping via pl.when, and
GQA handled by the K/V index_map (head h reads kv head h//G: no broadcast
materializes).

This is the serving/forward path; training backward uses the XLA attention
(a flash backward kernel is the natural next step).  Validated against
ref.py's oracle in interpret mode (tests/test_flash_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import round_up as _round_up

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, Sq: int, Skv: int,
            kv_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) - kv_offset

    run = True
    if causal:
        # skip blocks strictly above the diagonal
        run = (ik * bk - kv_offset) <= (iq * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]                     # (bq, hd)
        k = k_ref[0, 0]                     # (bk, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        valid = k_pos < Skv
        if causal:
            valid = valid & (k_pos <= q_pos)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                 # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0, 1.0, l)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "kv_offset", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    kv_offset: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (B, Sq, H, hd); k/v: (B, Skv, KV, hd) -> (B, Sq, H, hd).

    kv_offset: global position of kv token 0 relative to q token 0.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5

    bq = min(block_q, _round_up(Sq, 8))
    bk = min(block_k, _round_up(Skv, 128))
    Sqp = _round_up(Sq, bq)
    Skp = _round_up(Skv, bk)
    # layout: (B, heads, seq, hd) blocks
    qt = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    kt = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Skp - Skv), (0, 0)))
    vt = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Skp - Skv), (0, 0)))

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, bq=bq, bk=bk, Sq=Sq, Skv=Skv,
        kv_offset=kv_offset)
    out = pl.pallas_call(
        kern,
        grid=(B, H, Sqp // bq, Skp // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)


def flash_hbm_bytes(B, Sq, Skv, H, KV, hd, bytes_per_el=2) -> int:
    """Analytic HBM traffic of the kernel: q+o once, k/v per q-block pass.

    With bq=512, a (B,H) slice re-reads K/V Sq/bq times; causal halves it.
    Used by the kernel-adjusted roofline rows (§Perf C).
    """
    q_o = 2 * B * Sq * H * hd * bytes_per_el
    passes = max(1, Sq // 512)
    kv = 2 * B * Skv * KV * hd * bytes_per_el * passes * H // KV
    return q_o + kv // 2  # causal skips ~half the kv blocks
