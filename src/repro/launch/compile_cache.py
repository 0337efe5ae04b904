"""JAX's persistent compilation cache for the serving entry points.

One rule, applied by :func:`enable` before the first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
  sets no other path;
* otherwise compiled programs go to ``<checkout>/.jax_cache`` — a fixed
  path (the cache key includes it, so a moving directory never hits),
  listed in ``.gitignore``.  The package must then be imported from a
  checkout (``<checkout>/src/repro``); an installed copy raises instead
  of writing into the Python environment.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root when imported from one: src/repro/launch/ -> up three
CHECKOUT = Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> Path:
    """``<checkout>/.jax_cache``; raises unless :data:`CHECKOUT` really
    is the checkout (``pyproject.toml`` beside ``src/repro``)."""
    if not ((CHECKOUT / "pyproject.toml").is_file()
            and (CHECKOUT / "src" / "repro").is_dir()):
        raise RuntimeError(
            f"repro is not imported from a checkout ({CHECKOUT} holds no "
            f"pyproject.toml beside src/repro); set {ENV} to choose the "
            "compile cache directory")
    return CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(default_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["default_dir", "enable"]
