"""Virtual-CPU platform pinning — the ONE canonical copy of the recipe
used by tests/conftest.py, __graft_entry__.py, bench.py and the
multiprocess test workers (SURVEY.md §4: N virtual devices stand in for
N chips).

From outside a process, ``JAX_PLATFORMS=cpu`` plus
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` is all it takes.
``pin_virtual_cpu`` does the same from inside one, for entry points
that must work without those variables: it has to run before the first
JAX backend initialization, and it says so (returns False) when it came
too late.

Import-light on purpose: importing this module performs no JAX backend
work, so it is safe to use before pinning.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["with_device_count_flag", "pin_virtual_cpu"]

_FLAG = "xla_force_host_platform_device_count"


def with_device_count_flag(flags: str, n: Optional[int]) -> str:
    """Return XLA_FLAGS with the host-device-count flag token replaced
    by --xla_force_host_platform_device_count=n (n=None removes it)."""
    parts = [p for p in flags.split() if _FLAG not in p]
    if n is not None:
        parts.append(f"--{_FLAG}={n}")
    return " ".join(parts)


def pin_virtual_cpu(n: int) -> bool:
    """Try to pin an n-device virtual CPU platform in-process.

    Returns True on success; False if a JAX backend already exists with
    the wrong platform/device-count (the caller must then re-exec in a
    clean subprocess with JAX_PLATFORMS=cpu and the flag set)."""
    from jax._src import xla_bridge

    if xla_bridge._backends:  # backend(s) already initialized
        import jax
        devs = jax.devices()
        return devs[0].platform == "cpu" and len(devs) >= n

    os.environ["XLA_FLAGS"] = with_device_count_flag(
        os.environ.get("XLA_FLAGS", ""), n)

    import jax
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    return devs[0].platform == "cpu" and len(devs) >= n
