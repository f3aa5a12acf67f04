"""Persistent compilation cache placement — ONE rule for every entry point.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set in
code, so whoever launches the program places the cache. Unset: the cache goes
to ``<checkout>/.jax_cache`` — a fixed path derived from where the package
lives (the directory is part of the cache key, so a path that moves between
runs never hits). Entry points that compile (``chip_smoke.py``, ``bench.py``,
the examples through ``examples/_common.py``) call
:func:`enable_compile_cache` before their first compile; importing
``windflow_tpu`` as a library sets nothing.
"""

from __future__ import annotations

import os

#: <checkout>/.jax_cache (gitignored): the package's parent directory
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the one agreed place and
    return that directory. Call before the first compile."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
