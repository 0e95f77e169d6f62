"""Device policy: the one place that decides how kernels run.

* **Interpret or compile.**  A Pallas kernel compiles for the chip when
  JAX's default backend is a TPU and runs in the Pallas interpreter
  everywhere else (the CPU test runs).  The decision is made once per
  process, and no kernel entry point takes the flag from its caller, so a
  kernel never runs in the interpreter on a TPU.
* **Compile cache.**  Kernels compile for seconds each, so JAX's
  persistent compilation cache is pinned before the first kernel
  compiles: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the
  variable itself; nothing else is set), otherwise the fixed, git-ignored
  :data:`CHECKOUT_CACHE_DIR` inside the checkout.  The path never holds a
  temp name, a pid or a time, so a later process finds what an earlier
  one wrote.
"""

from __future__ import annotations

import functools
import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def platform() -> str:
    """JAX's default backend platform (``"tpu"``, ``"cpu"``, ...)."""
    import jax

    return str(jax.default_backend())


@functools.lru_cache(maxsize=None)
def interpret_default() -> bool:
    """True off-TPU (Pallas interpreter), False on a TPU (compiled).

    Every kernel entry point takes its ``interpret`` flag from here (none
    accepts one from its caller), so this also pins the compile cache
    before the first kernel compiles.
    """
    configure_compile_cache()
    return platform() != "tpu"


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives for this process."""
    return os.environ.get(CACHE_ENV) or str(CHECKOUT_CACHE_DIR)


@functools.lru_cache(maxsize=None)
def configure_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` (once)."""
    import jax

    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", path)
        # a compile before this call may have checked (and skipped) the
        # cache; reset so the next compile initializes it at ``path``
        compilation_cache.reset_cache()
    return path
