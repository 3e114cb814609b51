"""Where JAX keeps its persistent compilation cache for this repository.

A compiled solve loop at the paper's sizes takes seconds to build, and every
process starts with none.  JAX's persistent cache keeps them across
processes, but the cache path is part of what an entry is found by: a
directory that moves from run to run never hits.  So there is one place:

* ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
  variable itself, and nothing here overrides it);
* otherwise ``<repo>/.jax_cache``, a fixed path inside the checkout (listed
  in ``.gitignore``) — never a temporary directory, a process id or a time.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable_compile_cache` before they compile anything.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The cache directory: the environment's, else ``<repo>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or os.path.join(_REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir` and return
    that path.  Sets nothing when the environment already names it."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
