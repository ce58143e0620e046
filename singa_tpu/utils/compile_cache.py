"""Where the persistent XLA compile cache lives.

One rule, shared by every entry point that compiles for the chip
(chip_smoke.py, bench.py, tools/tpu_session.py, tools/loadgen.py):

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads the variable itself, so
  no path is set in code and whoever launched the process decides;
* otherwise ``<checkout>/.jax_cache``, one normalised string (the
  directory is part of the cache key, so two spellings of the same
  place never share entries).

TPU only.  XLA:CPU entries are compiled ahead of time for the CPU
features of the machine that wrote them; loaded elsewhere they risk
SIGILL and flood stderr with feature-mismatch warnings.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(platform: str) -> Optional[str]:
    """Turn the persistent compile cache on for `platform` (what
    ``jax.devices()[0].platform`` said).  Returns the directory in use,
    or None where the cache stays off."""
    if platform != "tpu":
        return None
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
