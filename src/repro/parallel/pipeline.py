"""GPipe-style pipeline parallelism via shard_map + ppermute.

Optional parallelism mode (DESIGN §5): layers split into S contiguous stages
whose parameters shard over a mesh axis (the "pod" axis on the multi-pod
mesh — inter-pod links carry only the (mb, seq, d_model) activations once
per tick, the pattern PP exists for).  Microbatches stream through the
classic GPipe schedule: T = M + S - 1 ticks, stage s working on microbatch
t - s at tick t; bubble fraction (S-1)/T.

The implementation is differentiable (ppermute transposes to the reverse
permute), so the same function serves the train step.  It is exercised by
tests on a host mesh and provable-by-compile on the production mesh via
``python -m repro.launch.dryrun_pp``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(
    stage_fn: Callable,        # (stage_params, x (mb, ...)) -> (mb, ...)
    mesh,
    stage_axis: str,
    n_microbatches: int,
):
    """Returns pipelined(params_stacked, x) with params leading dim = S.

    x: (batch, ...) with batch divisible by n_microbatches; params_stacked:
    pytree with leading stage dim S == mesh.shape[stage_axis].
    """
    from repro.parallel.halo import auto_axes
    mesh = auto_axes(mesh)
    S = mesh.shape[stage_axis]
    M = n_microbatches

    def local_fn(params_local, x_mb):
        # params_local: stage slice (leading dim 1); x_mb: (M, mb, ...) replicated
        params_local = jax.tree.map(lambda p: p[0], params_local)
        idx = jax.lax.axis_index(stage_axis)
        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        mb_shape = x_mb.shape[1:]
        state = jnp.zeros(mb_shape, x_mb.dtype)
        outs = jnp.zeros_like(x_mb)

        def tick(carry, t):
            state, outs = carry
            # stage 0 pulls microbatch t (clamped); others take the permuted state
            inp = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), keepdims=False)
            x = jnp.where(idx == 0, inp, state)
            y = stage_fn(params_local, x)
            nxt = jax.lax.ppermute(y, stage_axis, fwd_perm)
            # last stage commits microbatch t-(S-1)
            oi = t - (S - 1)
            commit = (idx == S - 1) & (oi >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(oi, 0, M - 1), axis=0)
            outs = jnp.where(commit, upd, outs)
            return (nxt, outs), None

        (state, outs), _ = jax.lax.scan(
            tick, (state, outs), jnp.arange(M + S - 1))
        # replicate the last stage's outputs to every stage
        mask = (idx == S - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * mask, stage_axis)
        return outs

    def pipelined(params_stacked, x):
        B = x.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by microbatches {M}")
        x_mb = x.reshape(M, B // M, *x.shape[1:])
        fn = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(P(stage_axis), P()), out_specs=P(),
                           check_vma=False)
        out = fn(params_stacked, x_mb)
        return out.reshape(B, *x.shape[1:])

    return pipelined


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-stacked."""
    def resh(p):
        L = p.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers not divisible into {n_stages} stages")
        return p.reshape(n_stages, L // n_stages, *p.shape[1:])
    return jax.tree.map(resh, stacked_params)
