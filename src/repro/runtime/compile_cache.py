"""JAX's persistent compilation cache for the entry points that run on a
chip (``chip_smoke.py``, the benchmark CLIs).

Called explicitly by those entry points — never on import and never in
tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing is set here.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is
part of what a later run must find again, so it is never derived from a
temporary directory, a process id or the time.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    # A profiler trace names each device operation by the scope path in
    # its executable's metadata (the engine's ``jax.named_scope`` phases).
    # JAX leaves that metadata out of the cache key by default, so a
    # cache shared with another version of the source would hand back an
    # executable that carries that version's scopes.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
