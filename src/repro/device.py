"""Device selection shared by the kernels and the entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax


def interpret() -> bool:
    """Pallas kernels compile natively on a TPU backend and run in
    interpret mode on any other."""
    return jax.default_backend() != "tpu"


def use_compile_cache(repo_root) -> str:
    """Persist compiled programs across processes: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it; otherwise
    use the fixed ``.jax_cache/`` under ``repo_root`` (a fixed path, so
    every run finds the entries of the last).  Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(repo_root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
