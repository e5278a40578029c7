"""Where JAX keeps its persistent compilation cache.

Process entry points (``python -m repro``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once, before their first
compile.  Nothing calls it at import, and the test suite leaves the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout (ignored by git): a cache directory that
# moves between runs never hits
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    return environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    JAX reads ``$JAX_COMPILATION_CACHE_DIR`` itself, so where it is set no
    other directory is configured here."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
