"""Persistent compilation cache placement — ONE rule for every entry point.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set in
code, so whoever launches the program places the cache. Unset: the cache goes
to ``<checkout>/.jax_cache`` — a fixed path derived from where the package
lives (the directory is part of the cache key, so a path that moves between
runs never hits). Entry points that compile (``chip_smoke.py``, ``bench.py``,
the examples through ``examples/_common.py``) call
:func:`enable_compile_cache` before their first compile; importing
``windflow_tpu`` as a library sets nothing.

The key of an entry includes the program's metadata (each operation's scope
path and source line). JAX's default strips it, so a program that differs
from a cached one only in its ``jax.named_scope``s would be handed the old
executable, and a profile of it would show the old scopes, or none: what
PR 25's first traced chip run did. A profile is read by those names
(docs/ARCHITECTURE.md, tracing), so they are part of what is cached. The
location of an operation is then its own source line, not the Python stack
that led to it (``jax_traceback_in_locations_limit`` 1): two chains of one
process built from different call sites run the same program, and the second
must find the first's executable (the benchmark's measured pipeline after its
throw-away one; a fresh compile inside its window fails the run). Not
``jax_include_full_tracebacks_in_locations`` off, which has the same effect on
the key and also drops the scope path from every operation's name. The price:
a checkout whose operator source lines moved compiles once more.
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
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
