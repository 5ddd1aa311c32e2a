"""JAX's persistent compilation cache, kept at one fixed place.

A compiled program is found again only under the same cache path, so the
path never depends on a temporary directory, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def checkout_cache(module: Path = Path(__file__)) -> Path:
    """``<checkout>/.jax_cache`` (listed in .gitignore), where the checkout
    holds this package as ``src/repro``.  The launchers run from a
    checkout (or an editable install of one); installed elsewhere, this
    refuses rather than write a cache beside site-packages."""
    root = module.resolve().parents[3]
    if not (root / "src" / "repro").is_dir():
        raise RuntimeError(
            f"{root} is not a checkout of this repository (no src/repro); "
            f"run from a checkout or set JAX_COMPILATION_CACHE_DIR")
    return root / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its path.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here; otherwise the cache is ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(checkout_cache())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
