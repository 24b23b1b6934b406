"""Where XLA's persistent compile cache lives — decided here and nowhere else.

A first TPU compile of a model graph takes tens of seconds, and every worker
process (``io/serving_worker``, ``tuning/trial_worker``) is a new interpreter
that would pay it again. JAX's own persistent cache shares compiled programs
between processes, keyed by the program, the toolchain and the cache's path,
so the path must not move from one process or one run to the next:

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory is used and no other is
  set — whoever runs the program places the cache;
- unset: ``<checkout>/.jax_cache``, a fixed, git-ignored directory beside
  the package.

The choice is written back to the environment, so child processes inherit it
without being told. jax reads the variable when it is imported; a jax that
was imported first is pointed at the directory through its config. No jax
import happens here (the package stays jax-free at import).
"""

from __future__ import annotations

import os
import sys

__all__ = ["place_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Decide the cache directory (see module docstring); returns it."""
    path = os.environ.get(_ENV)
    if not path:
        path = os.environ[_ENV] = os.path.join(_CHECKOUT, ".jax_cache")
    jax = sys.modules.get("jax")
    if jax is not None and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
