"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and no
other directory is set in code. Otherwise, on a TPU, the cache lives at a
fixed directory inside the checkout (``.jax_cache/``, listed in
``.gitignore``): the path is part of the cache key, so a directory that
moved would never hit. Off the TPU nothing is cached (CPU compiles are
cheap, and reloaded XLA:CPU entries only add loader warnings)."""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str | None:
    """Point the cache at its directory; returns the directory in use
    (None: no cache). Initializes the backend, so call it after anything
    that must precede that (XLA_FLAGS)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
