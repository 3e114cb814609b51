"""Inputs drawn from the seed, and the work they stand for.

Everything a run feeds the solver comes from ``--seed``: the same seed gives
the same fields, the same sampled ids and the same request order.  Fields
are drawn on the device in one jitted call (no host copy of a 2 GB batch);
served requests are host arrays, since that is what a client sends.
"""
from __future__ import annotations

import math

import numpy as np


def random_field(shape, seed: int, dtype="float32"):
    """Uniform [0, 1) data of ``shape``, drawn on the device from ``seed``."""
    import jax
    import jax.numpy as jnp
    draw = jax.jit(lambda k: jax.random.uniform(k, shape, jnp.float32)
                   .astype(dtype))
    return draw(jax.random.key(seed))


def rng(seed: int, stream: int) -> np.random.Generator:
    """A host generator for one purpose (``stream``) of one run's seed."""
    return np.random.default_rng([seed, stream])


def sample_ids(n: int, k: int, seed: int) -> np.ndarray:
    """``k`` distinct ids out of ``n``, sorted, drawn from the seed."""
    return np.sort(rng(seed, 1).choice(n, min(k, n), replace=False))


def point_sweeps(instances: int, grid, sweeps: int) -> int:
    """Points updated by ``sweeps`` sweeps over ``instances`` grids: the
    paper's Eq. 1 problem size (every grid element, shell included) times
    the iterations.  It counts the problem, not what an encoding computes,
    so it reads the same whatever backend runs the sweep."""
    return instances * math.prod(grid) * sweeps
