"""JAX persistent compilation cache for the entry points (train, serve,
``chip_smoke.py``).

A cache entry's key includes the cache directory, so the directory must not
move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
reads it and nothing else is configured; otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``. Called from the entry points' ``main``,
never at import, so library users and tests keep JAX's defaults.

A Pallas kernel's Mosaic body rides inside its custom call with its
tracebacks' file names, which the cache key hashes (JAX strips locations
only from the outer program). ``canonicalize_source_paths`` makes those
names relative to the checkout, so a program that holds a kernel loads from
the cache in a checkout at another path; ``launch/train.py build_pipeline``
calls it too, since the GRPO grad step holds the SPA kernels.
"""
from __future__ import annotations

import os
import pathlib
import re
from typing import Optional

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on for every compile (however short) and
    return the directory it writes to. On the CPU backend it stays off:
    compiles there are cheap, and XLA:CPU warns about host features each
    time it reloads a cached executable."""
    canonicalize_source_paths()
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:        # JAX reads the variable itself when it is set
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def canonicalize_source_paths() -> None:
    """Write source paths into lowered programs relative to the checkout."""
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT)) + "/")
