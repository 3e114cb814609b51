"""Flash attention backward Pallas kernels (FA-2 style, arXiv:2307.08691).

Completes §Perf C: with forward + backward kernels the (B,H,S,S) probability
tensors never touch HBM in training either.  Scheme:

  forward extras : lse row statistics (m + log l), saved with q,k,v,o
  dq kernel      : grid (B,H,iq,ik), kv innermost, accumulates dq in VMEM
  dkv kernel     : grid (B,KV,g,ik,iq), q innermost, accumulates dk/dv in
                   VMEM; the GQA group dim g folds into the accumulation
                   (no (B,S,H,hd)-sized dk materializes)

Both recompute p = exp(q·kᵀ·scale − lse) blockwise from the saved lse — the
flash trick: O(S²) recompute, O(S) storage.  ``flash_attention_grad``
assembles them into a jax.custom_vjp op validated against the XLA oracle's
gradients (tests/test_flash_attention.py::TestFlashBackward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import round_up as _round_up

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward with lse output
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale, causal, bq, bk, Sq, Skv, kv_offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) - kv_offset
    run = (ik * bk - kv_offset) <= (iq * bq + bq - 1) if causal else True

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = k_pos < Skv
        if causal:
            valid = valid & (k_pos <= q_pos)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
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
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_safe)


def _flash_fwd(q, k, v, *, causal, scale, bq, bk, kv_offset, skv_true):
    B, H, Sqp, hd = q.shape
    Skp = k.shape[2]
    G = H // k.shape[1]
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                             bk=bk, Sq=Sqp, Skv=skv_true, kv_offset=kv_offset)
    interpret = jax.default_backend() == "cpu"
    o, lse = pl.pallas_call(
        kern,
        grid=(B, H, Sqp // bq, Skp // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, iq, ik: (b, h, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sqp, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sqp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# dq kernel: grid (B, H, iq, ik), kv innermost
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, bq, bk, Skv, kv_offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) - kv_offset
    run = (ik * bk - kv_offset) <= (iq * bq + bq - 1) if causal else True

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = k_pos < Skv
        if causal:
            valid = valid & (k_pos <= q_pos)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])                  # (bq, bk)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# dk/dv kernel: grid (B, KV, G, ik, iq), q innermost; dk/dv accumulate over
# both iq and the GQA group dim g
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, bq, bk,
                Skv, kv_offset, n_g):
    ik = pl.program_id(2)
    g = pl.program_id(3)
    iq = pl.program_id(4)

    first = (g == 0) & (iq == 0)

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) - kv_offset
    run = (ik * bk - kv_offset) <= (iq * bq + bq - 1) if causal else True

    @pl.when(run)
    def _block():
        q = q_ref[0, 0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = k_pos < Skv
        if causal:
            valid = valid & (k_pos <= q_pos)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0, 0][:, None])               # (bq, bk)
        # dv += p^T dO
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, 0][:, None]) * scale      # (bq, bk)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    last = (g == n_g - 1) & (iq == pl.num_programs(4) - 1)

    @pl.when(last)
    def _flush():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, *, causal, scale, bq, bk, kv_offset,
               skv_true):
    """All arrays in (B, heads, seq, hd) layout (padded)."""
    B, H, Sqp, hd = q.shape
    KV, Skp = k.shape[1], k.shape[2]
    G = H // KV
    interpret = jax.default_backend() == "cpu"
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, Skv=skv_true, kv_offset=kv_offset),
        grid=(B, H, Sqp // bq, Skp // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, iq, ik: (b, h, iq)),
            pl.BlockSpec((1, 1, bq), lambda b, h, iq, ik: (b, h, iq)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # q reshaped to (B, KV, G, Sq, hd) so the group dim is a grid axis
    q5 = q.reshape(B, KV, G, Sqp, hd)
    do5 = do.reshape(B, KV, G, Sqp, hd)
    lse5 = lse.reshape(B, KV, G, Sqp)
    delta5 = delta.reshape(B, KV, G, Sqp)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, Skv=skv_true, kv_offset=kv_offset, n_g=G),
        grid=(B, KV, Skp // bk, G, Sqp // bq),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd),
                         lambda b, kv, ik, g, iq: (b, kv, g, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, kv, ik, g, iq: (b, kv, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, kv, ik, g, iq: (b, kv, ik, 0)),
            pl.BlockSpec((1, 1, 1, bq, hd),
                         lambda b, kv, ik, g, iq: (b, kv, g, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b, kv, ik, g, iq: (b, kv, g, iq)),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b, kv, ik, g, iq: (b, kv, g, iq)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, ik, g, iq: (b, kv, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, ik, g, iq: (b, kv, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Skp, hd), k.dtype),
            jax.ShapeDtypeStruct((B, KV, Skp, hd), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q5, k, v, do5, lse5, delta5)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper (the trainable op)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_trainable(q, k, v, causal=True, block_q=512, block_k=512,
                              kv_offset=0):
    out, _ = _fwd_rule(q, k, v, causal, block_q, block_k, kv_offset)
    return out


def _layout(q, k, v, block_q, block_k):
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    bq = min(block_q, _round_up(Sq, 8))
    bk = min(block_k, _round_up(Skv, 128))
    Sqp, Skp = _round_up(Sq, bq), _round_up(Skv, bk)
    qt = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    kt = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Skp - Skv), (0, 0)))
    vt = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Skp - Skv), (0, 0)))
    return qt, kt, vt, bq, bk


def _fwd_rule(q, k, v, causal, block_q, block_k, kv_offset):
    B, Sq, H, hd = q.shape
    scale = hd ** -0.5
    qt, kt, vt, bq, bk = _layout(q, k, v, block_q, block_k)
    o, lse = _flash_fwd(qt, kt, vt, causal=causal, scale=scale, bq=bq, bk=bk,
                        kv_offset=kv_offset, skv_true=k.shape[1])
    out = o[:, :, :Sq].transpose(0, 2, 1, 3)
    return out, (q, k, v, o, lse)


def _bwd_rule(causal, block_q, block_k, kv_offset, res, dout):
    q, k, v, o, lse = res
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = hd ** -0.5
    qt, kt, vt, bq, bk = _layout(q, k, v, block_q, block_k)
    Sqp = qt.shape[2]
    dot = jnp.pad(dout.transpose(0, 2, 1, 3),
                  ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    dq, dk, dv = _flash_bwd(qt, kt, vt, o, lse, dot, causal=causal,
                            scale=scale, bq=bq, bk=bk, kv_offset=kv_offset,
                            skv_true=Skv)
    dq = dq[:, :, :Sq].transpose(0, 2, 1, 3)
    dk = dk[:, :, :Skv].transpose(0, 2, 1, 3)
    dv = dv[:, :, :Skv].transpose(0, 2, 1, 3)
    return dq, dk, dv


flash_attention_trainable.defvjp(_fwd_rule, _bwd_rule)
