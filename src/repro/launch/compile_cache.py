"""JAX's persistent compilation cache: one policy for every entry point.

The entry points (``examples/ssl_pretrain.py``, ``launch/train.py``,
``serve/cli.py``, ``benchmarks/run.py``, ``chip_smoke.py``) call
``enable_compile_cache()`` first thing in ``main``, so a second run of any of
them reads compiled executables back instead of compiling again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore).  Fixed, never derived from a
# temp name, a pid or the time: entries are found again only under the path
# they were written to.
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as its
    ``jax_compilation_cache_dir`` option: it is used as it is and nothing is
    set here.  Otherwise the cache goes to ``CHECKOUT_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
