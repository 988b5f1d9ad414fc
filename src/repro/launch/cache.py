"""JAX's persistent compilation cache, pointed at one fixed place.

Launchers and ``chip_smoke.py`` call ``enable_compile_cache`` before their
first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives in
``.jax_cache`` at the root of the checkout this package is imported from —
a fixed path, because the path is part of what a cached entry is found by.
Imported from anywhere else (an installed copy), the package has no
checkout to write into, and the cache stays off.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax


def checkout_root() -> Optional[pathlib.Path]:
    """The checkout ``src/repro`` sits in, or None for an installed copy."""
    root = pathlib.Path(__file__).resolve().parents[3]
    return root if (root / "pyproject.toml").is_file() \
        and (root / "src" / "repro").is_dir() else None


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory, or
    None when it stays off (no variable set, no checkout)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    root = checkout_root()
    if root is None:
        return None
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
