"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``examples/tune_sapphire.py``, ``python -m repro.service``) call
:func:`use_compile_cache` before their first compile; importing the
library never does.  The directory is part of the cache's key, so it never
moves between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  here sets another directory;
* otherwise: ``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
