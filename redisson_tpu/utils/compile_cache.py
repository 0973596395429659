"""Where JAX's persistent compilation cache lives — the one place that
decides it for every entry point (server, bench, chip smoke, tests).

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own reading and stands
untouched.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the path is part of the cache key — a directory that
moves between runs never hits."""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Arm the persistent compile cache; returns the directory in use.
    Call before the first compile (it imports jax, never a backend)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
