"""Where the entry points keep JAX's persistent compilation cache.

Each entry point (``chip_smoke.py``, ``launch/spconv_serve.py``,
``launch/train.py``, ``launch/spconv_stream.py``) calls
:func:`setup_compile_cache` once from its ``main`` — never at import, so
a library user or a test process keeps whatever JAX was configured with.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory (listed in .gitignore). Fixed, not
#: derived from a temp name, pid or time: the path is part of what the
#: cache is keyed on, so a directory that moves never hits.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is set here. Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
