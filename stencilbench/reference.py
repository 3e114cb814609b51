"""The plain reference that decides ``correct``: Jacobi sweeps of the
paper's Laplace stencil by shifted adds, with Dirichlet masking.

It is the benchmark's own copy of the program's oracle
(``src/repro/core/reference.py``), written again so that the yardstick
imports nothing of the program: no spec, no boundary class, no plan.

Semantics, as the solver's documentation states them:

* a call first writes the Dirichlet value onto the shell of its input, then
  runs its sweeps; a sweep is ``y = sum_k w * shift(x, off_k)`` with zero
  fill outside the grid, then the shell is set to the Dirichlet value again;
* a solve to tolerance runs chunks of ``check_every`` sweeps and, after each,
  freezes every instance whose update meets
  ``||y - x||_2 <= atol + rtol * ||y||_2`` over its grid; the count of
  sweeps an instance ran is ``check_every`` times the chunks it was active.

Everything runs in float32 under ``highest`` matmul precision, in jitted
loops over a leading instance axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def laplace_taps(ndim: int) -> tuple[tuple[tuple[int, ...], float], ...]:
    """The ``2 * ndim`` neighbour offsets of the Laplace Jacobi stencil,
    each weighted ``1 / (2 * ndim)``."""
    taps = []
    for d in range(ndim):
        for s in (-1, 1):
            off = [0] * ndim
            off[d] = s
            taps.append((tuple(off), 1.0 / (2 * ndim)))
    return tuple(taps)


def _shift(x, offset):
    """``x`` shifted over its trailing axes so ``out[i] = x[i + offset]``,
    zero-filled at the edges."""
    lead = x.ndim - len(offset)
    for d, o in enumerate(offset):
        if o == 0:
            continue
        ax = lead + d
        n = x.shape[ax]
        sl = [slice(None)] * x.ndim
        pad = [(0, 0)] * x.ndim
        if o > 0:
            sl[ax] = slice(o, n)
            pad[ax] = (0, o)
        else:
            sl[ax] = slice(0, n + o)
            pad[ax] = (-o, 0)
        x = jnp.pad(x[tuple(sl)], pad)
    return x


def _interior(grid, dtype):
    """1 inside, 0 on the outermost shell of a grid."""
    inside = jnp.ones(grid, bool)
    for d, n in enumerate(grid):
        i = jax.lax.broadcasted_iota(jnp.int32, grid, d)
        inside = inside & (i >= 1) & (i < n - 1)
    return inside.astype(dtype)


def _sweep(x, taps, mask, bc):
    acc = jnp.zeros_like(x)
    for off, w in taps:
        acc = acc + jnp.asarray(w, x.dtype) * _shift(x, off)
    return acc * mask + bc * (1 - mask)


@functools.partial(jax.jit, static_argnames=("ndim", "dtype"))
def _fixed(x, sweeps, bc, *, ndim, dtype):
    x = x.astype(dtype)
    grid = x.shape[-ndim:]
    mask = _interior(grid, dtype)
    bc = jnp.asarray(bc, dtype)
    taps = laplace_taps(ndim)
    x = x * mask + bc * (1 - mask)
    return jax.lax.fori_loop(0, sweeps,
                             lambda _, t: _sweep(t, taps, mask, bc), x)


def sweeps(x, ndim: int, bc: float, n: int, dtype="float32"):
    """``n`` Jacobi sweeps of every instance of ``x`` ((batch, *grid))."""
    with jax.default_matmul_precision("highest"):
        return _fixed(x, n, bc, ndim=ndim, dtype=jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnames=("ndim", "check_every",
                                             "max_chunks"))
def _converge(x, bc, rtol, atol, *, ndim, check_every, max_chunks):
    x = x.astype(jnp.float32)
    grid = x.shape[-ndim:]
    axes = tuple(range(1, x.ndim))
    mask = _interior(grid, jnp.float32)
    bc = jnp.asarray(bc, jnp.float32)
    taps = laplace_taps(ndim)

    def chunk(t):
        t = t * mask + bc * (1 - mask)
        return jax.lax.fori_loop(0, check_every,
                                 lambda _, u: _sweep(u, taps, mask, bc), t)

    def norm(v):
        return jnp.sqrt(jnp.sum(v * v, axis=axes))

    def cond(s):
        k, _, active, _ = s
        return (k < max_chunks) & jnp.any(active)

    def body(s):
        k, x, active, iters = s
        y = chunk(x)
        done = norm(y - x) <= atol + rtol * norm(y)
        keep = active.reshape(active.shape + (1,) * (x.ndim - 1))
        x = jnp.where(keep, y, x)
        iters = iters + jnp.where(active, check_every, 0)
        return k + 1, x, active & ~done, iters

    b = x.shape[0]
    _, x, active, iters = jax.lax.while_loop(
        cond, body, (jnp.int32(0), x, jnp.ones((b,), bool),
                     jnp.zeros((b,), jnp.int32)))
    return x, iters, ~active


def converge(x, ndim: int, bc: float, *, rtol: float, atol: float = 0.0,
             check_every: int, max_iters: int):
    """Solve every instance of ``x`` to tolerance; returns
    ``(x, iterations, converged)``."""
    with jax.default_matmul_precision("highest"):
        return _converge(x, bc, rtol, atol, ndim=ndim,
                         check_every=check_every,
                         max_chunks=max(1, max_iters // check_every))
