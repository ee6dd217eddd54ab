"""Small dependency-free utilities shared across entry points.

Nothing here may import jax (directly or transitively): the helpers run
before the JAX backend initialises, and some callers rely on that window.
"""
from __future__ import annotations

import os


def force_host_device_count(n: int) -> bool:
    """Append ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``.

    Gives a CPU-only process an n-way device mesh (the code path a TPU pod
    takes, minus the speed). Only effective before the JAX *backend*
    initialises — importing jax is fine, touching a device is not — so
    call it before the first array op. A no-op if ``n <= 1`` or the flag
    is already set (an operator-provided count wins). Returns whether the
    flag was applied. ``tests/conftest.py`` intentionally inlines the same
    three lines — it must run before any import graph.
    """
    if n <= 1:
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return False
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())
    return True


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives.

    ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself, and
    the entry points then set no other); otherwise one fixed directory in
    the checkout, ``.jax_cache/`` (git-ignored). The path must not move
    between runs for a later run to hit what an earlier one compiled.
    """
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
