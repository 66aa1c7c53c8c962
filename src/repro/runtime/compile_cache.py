"""Where JAX keeps its persistent compilation cache for this checkout.

A compile cache only hits when its directory stays put between processes, so
the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (jax reads that variable itself; nothing here overrides it), otherwise
``<checkout>/.jax_cache``, resolved from this file's own location.  The entry
points (`chip_smoke.py`, `launch.serve`, `launch.loadtest`, `benchmarks.run`)
call `enable_compile_cache()` first thing; library code never does.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/src/repro/runtime/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def compile_cache_dir(environ=None) -> str:
    """The cache directory: the environment's, else the checkout's."""
    env = os.environ if environ is None else environ
    return env.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point jax's persistent cache at `compile_cache_dir()`; return it.

    Every compiled program is written (the default skips programs that
    compile in under a second, which is most decode kernels).
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


__all__ = ["compile_cache_dir", "enable_compile_cache", "DEFAULT_DIR"]
