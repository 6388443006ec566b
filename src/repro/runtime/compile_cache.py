"""Where jax keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as jax itself reads
it and nothing here overrides it.  Otherwise, on an accelerator, the
cache goes to one fixed directory inside the checkout
(:data:`DEFAULT_DIR`, gitignored), so that every process of one checkout
shares its compiles: the directory path is part of the cache key, so a
moving default would never hit.  The CPU backend (the tests) gets no
default: its compiles take seconds, and XLA:CPU logs a spurious
host-feature mismatch for every entry it reads back.

Call :func:`configure` before the process compiles anything — jax decides
on its first compile whether a cache is in use.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> Optional[str]:
    """Point jax's persistent cache at its directory; returns it (None:
    no persistent cache)."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if (not jax.config.jax_compilation_cache_dir
            and jax.default_backend() != "cpu"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir
