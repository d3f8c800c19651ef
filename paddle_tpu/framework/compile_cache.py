"""Where JAX's persistent compilation cache lives.

Every chip-tool call starts on a fresh machine, and every process of a
command compiles the same programs again; a cold gpt3_1p3b train step plus
the serving programs is minutes of XLA/Mosaic compile. The entry points
(chip_smoke.py, benchmark/run.py) call `place_compile_cache()` once, before
their first compilation.

The cache directory is part of the cache key, so it must not move between
runs: where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and this
module sets nothing in code; otherwise the directory is `.jax_cache` at the
root of this checkout (git-ignored) — never a temp dir, a pid or `~`.
"""

from __future__ import annotations

import os

import jax

__all__ = ["place_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed place and
    return the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
