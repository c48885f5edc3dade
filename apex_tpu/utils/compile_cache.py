"""Where this checkout keeps what it compiles and tunes.

A cold process compiles every program it runs (the BERT-Large train
step alone takes minutes), so entry points — ``chip_smoke.py``,
``bench.py``, the ``bench_configs.py`` legs, the examples' ``main()``
— call :func:`enable_compile_cache` once before their first jit.
Never called while ``apex_tpu`` is imported: a library import must
not change where a host program caches.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT", "enable_compile_cache"]

#: the checkout this file lives in (``<checkout>/apex_tpu/utils/``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn jax's persistent compilation cache on; returns its
    directory.  ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads
    it itself — nothing is set here); otherwise the cache lives at the
    fixed path ``<checkout>/.jax_cache``.  The path is part of the
    cache key's surroundings: a directory that moves never hits, so no
    temporary names, pids or timestamps."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
