"""Where JAX's persistent compilation cache lives for the entry points.

The search and serving programs at the paper's widths take tens of seconds
to compile, so every entry point (``chip_smoke.py``, ``launch/search.py``,
``launch/serve.py``, ``launch/selfplay.py``) calls ``enable_compile_cache``
at the start of its ``main()`` — never on import. A cache only hits when
its path is stable, so the default path is fixed inside the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it as
    its own setting and nothing here overrides it. Otherwise the cache goes
    to ``DEFAULT_CACHE_DIR``.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
