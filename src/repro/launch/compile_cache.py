"""Where JAX keeps its persistent compilation cache.

One rule, applied by the programs that drive a chip (`launch/serve.py`
and `chip_smoke.py`) and never at import time:

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and this
    module sets nothing.
  * unset: the cache lives at the fixed `<checkout>/.jax_cache`
    (listed in `.gitignore`). The path is fixed, never a temp, pid or
    time-based one, so a second run from the same checkout finds the
    entries the first one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see
    the module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
