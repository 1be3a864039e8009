"""One persistent XLA compile cache for every entry point of this repo.

``chip_smoke.py``, the bench children, ``dstpu_prewarm`` and the examples
all call :func:`configure_compile_cache` before their first compile:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  this sets no directory — whoever launched the process placed the cache;
- where it is not, the cache goes to one fixed path inside the checkout
  (``<repo>/.jax_cache``, gitignored). The path is part of the cache key,
  so it is never built from a temp name, a pid, a worker id or a time;
- ``cache_dir`` is the explicit override of a command-line flag
  (``dstpu_prewarm --cache-dir``) and wins over both.

Every program is persisted whatever it took to compile, in all three
cases: a serving tick family has many small programs, and a cold start
that still compiles those is not a warm start.

``tests/conftest.py`` does NOT use this: it wipes its own session-scoped
directory at start and must never be pointed at a cache that outlives it.
"""

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def configure_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Place the persistent compile cache (see module docstring) and
    return the directory in use."""
    import jax

    if cache_dir is None and not os.environ.get(ENV_VAR):
        cache_dir = DEFAULT_DIR
    if cache_dir is not None and cache_dir != jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # a cache instance that already opened another directory ignores
        # the config update until it is reset
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
