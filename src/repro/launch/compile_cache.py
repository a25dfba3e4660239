"""JAX persistent compilation cache at one fixed place.

Every entry point that compiles (``chip_smoke.py``, ``merge_cli`` and the
shard worker ``python -m repro.launch.worker``) calls
:func:`enable_compile_cache` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and no other directory is set.  Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path, because the path is part of what
a later run must find again.
"""
from __future__ import annotations

import os

#: the checkout this package runs from (``<checkout>/src/repro/launch``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Every compile is kept, however short: the merge kernels compile in
    well under JAX's default one-second floor."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
