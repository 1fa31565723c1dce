"""JAX's persistent compilation cache, placed from outside or at one fixed path.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``tools/filterctl.py``,
the examples) call :func:`enable_compile_cache` and :func:`disable_tpu_logs`
before JAX first touches a device. Importing the library never does, and
neither do the tests.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set here.
* Otherwise the cache lives in ``<checkout>/.jax_cache/`` (git-ignored). The
  path is fixed because it is part of the cache key: a directory that moves
  between runs never hits.
"""

from __future__ import annotations

import os
import pathlib
from typing import Mapping, MutableMapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
TPU_LOG_VAR = "TPU_LOG_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir(environ: Mapping[str, str] = os.environ) -> Optional[str]:
    """The directory this module would configure, or None to leave it to JAX."""
    if environ.get(ENV_VAR):
        return None
    return str(DEFAULT_DIR)


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent cache at :data:`DEFAULT_DIR` unless the
    environment already places it. Returns the directory it set, or None."""
    path = cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def disable_tpu_logs(environ: MutableMapping[str, str] = os.environ) -> str:
    """Turn libtpu's log files off unless the caller placed them.

    libtpu otherwise writes them under ``/tmp``, outside the checkout. It reads
    the variable when it loads, so call this before the first device use.
    Returns the value in effect.
    """
    return environ.setdefault(TPU_LOG_VAR, "disabled")
