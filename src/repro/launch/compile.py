"""Compilation bookkeeping shared by the entry points: where JAX's
persistent compilation cache lives, and how many programs were compiled.

``setup_compile_cache`` is called by ``launch.train``, ``launch.serve``
and ``chip_smoke.py`` before their first compile.  ``CompileCounter``
counts every executable JAX builds (a jit cache miss, whether XLA then
compiles it or loads it from the persistent cache), so a loop can assert
that its steady state compiles nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax import monitoring

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Fixed, git-ignored path inside the checkout: the cache's path is part
# of what makes a later run find its entries again.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

# The event JAX records around building each executable (XLA compile or
# persistent-cache load), once per jit cache miss.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.
    """
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


class CompileCounter:
    """Context manager counting executables built inside its block.

    >>> with CompileCounter() as cc:
    ...     _ = jax.jit(lambda x: x + 1)(1.0)
    >>> cc.count
    1
    """

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listener(self, event: str, duration: float, **kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileCounter":
        monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc) -> bool:
        monitoring.unregister_event_duration_listener(self._listener)
        return False
