"""One persistent compilation cache for every entry point.

A cold run compiles every program it executes; on a TPU that is often
most of a short run. JAX's persistent cache keeps the executables on
disk, so a later process that finds the same directory loads them
instead. The entry points (``launch/train.py``, ``launch/serve.py``,
``launch/serve_policy.py``, ``benchmarks/run.py`` and ``chip_smoke.py``)
call ``enable()`` once from ``main``. Importing this module changes
nothing, so library users and the tests keep JAX's own settings.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed path inside the checkout (``.gitignore`` lists it): the
# directory is part of what a later run must find again, so it never
# carries a temporary name, a pid or a timestamp
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    the variable itself and no other is set here. Otherwise the cache
    sits at ``DEFAULT_DIR``. Every executable is kept, not only those
    that took a second or more to compile: a run on the chip compiles
    dozens of small programs, and loading each is cheaper.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
