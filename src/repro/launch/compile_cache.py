"""JAX's persistent compilation cache for the entry points.

Entry points call :func:`enable_compile_cache` once at start-up; importing
this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping

import jax

#: The checkout root: this file lives at ``src/repro/launch/``.
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when it is set, else ``.jax_cache/``
    at the checkout root.  The fallback is fixed, never temporary or per
    process, so a later run from the same checkout finds what this one
    compiled."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
