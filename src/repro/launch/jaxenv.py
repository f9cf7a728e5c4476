"""Process-level JAX set-up that only entry points call, never an import.

* :func:`use_compile_cache` places JAX's persistent compilation cache.
  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is changed
  (JAX reads the variable itself); otherwise the cache goes to the fixed
  path :data:`CACHE_DIR` at the repository root. The directory is part
  of the cache key, so it never depends on a temporary directory, a
  process id or the time.
* :func:`virtual_cpu_devices` gives the CPU backend ``n`` virtual devices
  for the multi-shard demos and smokes, and only under
  ``JAX_PLATFORMS=cpu``. On an accelerator the visible devices are used
  as they are, in this one process: a mesh wider than them is refused by
  the engine, and no child process is started to fake one.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "use_compile_cache", "virtual_cpu_devices"]

#: compile-cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
#: (``<repo>/.jax_cache``, git-ignored).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    Returns the directory in use. Call once from an entry point, before
    the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def virtual_cpu_devices(n: int) -> bool:
    """Ask for ``n`` virtual CPU devices if ``JAX_PLATFORMS=cpu``.

    Must run before JAX initialises its backends. Returns whether the
    flag applies; an ``XLA_FLAGS`` device count set by the caller wins.
    """
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={n}".strip()
    return True
