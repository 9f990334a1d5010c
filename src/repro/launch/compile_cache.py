"""JAX's persistent compilation cache, placed once by each entry point.

``chip_smoke.py``, ``launch/serve.py`` and the benchmarks call
:func:`enable_compile_cache` once, before they compile anything. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the helper
sets nothing. Otherwise the cache lives at ``.jax_cache/`` in the root of
the checkout: a fixed path, because the directory is part of what makes a
later run find its entries again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["ENV_VAR", "cache_root", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[3]


def cache_root() -> Path:
    """The cache directory: the environment's, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else _CHECKOUT / ".jax_cache"


def enable_compile_cache(subdir: str = "") -> Path:
    """Turn the persistent cache on; return its directory.

    ``subdir`` places it in a named subdirectory of :func:`cache_root` (a
    benchmark arm that must start cold empties one first). With neither a
    ``subdir`` nor a fixed path to set — the environment names the cache —
    nothing is changed. Otherwise every compile is persisted, however small.
    """
    root = cache_root()
    if os.environ.get(ENV_VAR) and not subdir:
        return root
    path = root / subdir if subdir else root
    from jax.experimental.compilation_cache import compilation_cache

    # JAX opens the cache lazily at its first compile and keeps the handle:
    # drop it so a process that already compiled picks up the new path.
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
