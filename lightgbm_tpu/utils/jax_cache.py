"""Where the persistent XLA compile cache lives — decided from outside.

One helper, called by every entry point (``chip_smoke.py``, ``bench.py``'s
child, the CLI) before the first compile.  The cache directory is part of
the cache key's lookup, so it must not move between runs: it is either what
the caller's environment names, or one fixed path inside the checkout —
never ``~/.cache``, a temp name, a pid or a time.

(The serve AOT cache, ``serve/compile_cache.py``, is a different thing: a
content-keyed store of serialized serve executables that the USER points
at a directory with ``tpu_serve_compile_cache``.)
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this does
    nothing.  Unset: ``<checkout>/.jax_cache`` (git-ignored), with jax's
    one-second "worth caching" threshold off — a program that compiles in
    0.99 s one run and 1.01 s the next would otherwise enter the cache on
    the second run, and "a warm run adds no entries" could not be
    checked.

    Either way the cache key keeps the operations' metadata.  jax strips
    it by default, so two programs that differ only in their
    ``jax.named_scope`` paths (``telemetry.PHASES``) share one entry, and
    whichever compiled first decides the names a profiler trace of the
    other shows: a trace taken after a cache hit could read another
    version's phase names, or none."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_entry_count(path: str) -> int:
    """Cached executables under ``path`` (0 when it does not exist yet)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except OSError:
        return 0
