"""Where the entry points keep JAX's persistent compilation cache.

Library imports never configure the cache; scripts that own their process
and run the jax backend (``chip_smoke.py``, the benchmark CLIs, the
conformance CLI) call :func:`use_compile_cache` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["use_compile_cache"]


def use_compile_cache(checkout: str | os.PathLike) -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
    itself, so nothing else is set); otherwise the cache is
    ``<checkout>/.jax_cache``.  The path must not move between runs, since
    it is part of the cache's key.  Returns the directory in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(Path(checkout).resolve() / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path
