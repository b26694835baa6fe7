"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so it must not move between runs:
a directory named by ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads that
variable itself), and otherwise the cache sits at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(checkout: Path,
                      environ: Mapping[str, str] = os.environ
                      ) -> Optional[Path]:
    """The directory the caller should hand to JAX, or None when
    ``JAX_COMPILATION_CACHE_DIR`` is set and JAX already uses it."""
    if environ.get(ENV):
        return None
    return Path(checkout).resolve() / ".jax_cache"
