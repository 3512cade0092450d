"""Where JAX's persistent compilation cache lives.

A cold boot compiles a ladder of device programs (tens of seconds each
at a million subscriptions); the persistent cache turns the next boot's
compiles into reads. The directory is part of the cache key, so it must
not move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that itself — nothing is set in code),
and otherwise a fixed path inside the checkout.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Call once per entry point, before the first compile. Returns the
    directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
