"""Persistent compilation cache for the entry points (the serving CLI and
``chip_smoke.py``).

A full-width serving engine compiles one program per prefill bucket plus
its decode window; a cold start spends most of its set-up time there.
JAX's persistent cache keeps those executables on disk between runs.
Deliberately jax-free at import, like :mod:`repro.launch.fake_devices`.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache(default_dir: Path = DEFAULT_DIR) -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own
    and nothing is set here.  Otherwise the cache lives at a fixed
    directory of the checkout (``<repo>/.jax_cache``): a directory that
    moved between runs would never hit.
    """
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    path = str(Path(default_dir).resolve())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
