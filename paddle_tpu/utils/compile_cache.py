"""Where jax's persistent compilation cache lives.

The cache directory is part of the cache key, so a directory that moves
never hits.  Entry points that run on the chip (``paddle_tpu/cli.py``
``main``, ``chip_smoke.py``, ``bench.py``) call
:func:`configure_compile_cache` once, before the first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads it itself and this sets no
  directory in code, so whoever runs the program places the cache;
* unset — the fixed ``<checkout>/.jax_cache`` next to the package
  (git-ignored), the same path on every run of the same checkout.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
