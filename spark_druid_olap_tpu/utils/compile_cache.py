"""JAX's persistent compilation cache, turned on by process entry points.

Called from `chip_smoke.py`, `bench.py` `main()` and the historical's
`main()` — never at import and never from tests.  Where
`JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and no directory
is set in code; otherwise the cache lives at `<checkout>/.jax_cache`, a
fixed path (the path is part of the cache key: a directory that moves
never hits).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the cache on; returns the directory in effect."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # the SSB programs compile in well under the default 1 s floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
