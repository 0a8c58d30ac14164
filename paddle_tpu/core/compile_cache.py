"""Where the persistent XLA compilation cache lives.

The cache key includes the cache directory's path, so a directory that
moves between runs (a temp dir, a uid or pid in the name) never hits.
The rule, for every entry that compiles for the chip (``chip_smoke.py``,
``bench.py``, the TPU test lane): whoever starts the process decides
through ``JAX_COMPILATION_CACHE_DIR``, which jax reads itself; only when
that is unset do we name one fixed, git-ignored directory inside the
checkout.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Call before the first compile."""
    import jax

    # jax keeps programs that compiled in under a second out of the
    # cache. An engine warmup or a model init is a few large programs
    # and a couple of hundred such small ones; once the large ones hit,
    # the small ones are what a restart still waits for.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
