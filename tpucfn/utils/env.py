"""Child-process environments and the compile-cache location.

Every subprocess that must run on fake CPU devices (the driver's
multichip dryrun, the test suite) builds its child environment through
:func:`scrub_accelerator_env`, so the prefix list lives in exactly one
place: make a child run on N fake CPU devices whatever accelerator
variables the parent environment carries.

This module must stay importable with no dependencies (no jax, no
tpucfn package init): ``__graft_entry__.py`` and ``tests/conftest.py``
load it by file path before any backend decision is made.
"""

from __future__ import annotations

import os
from typing import Mapping

_ACCEL_ENV_PREFIXES = ("JAX_", "XLA_", "TPU_", "LIBTPU", "PJRT_", "PALLAS_")

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def xla_cache_dir() -> str:
    """The one resolution rule for the persistent XLA compile cache:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (placed from outside — the
    code then sets no other), else the fixed ``<checkout>/.cache/xla``.
    The path is part of the cache key's identity across runs, so it is
    never a temporary name.  Shared by obs.enable_compile_cache, the
    artifact store default and the dryrun child env."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
            or os.path.join(_CHECKOUT, ".cache", "xla"))


def scrub_accelerator_env(
    env: Mapping[str, str], n_devices: int | None = None
) -> dict[str, str]:
    """Return a copy of ``env`` with every accelerator-selection variable
    removed; with ``n_devices`` set, additionally pin the environment to
    ``n_devices`` fake CPU devices."""
    out = {
        k: v
        for k, v in env.items()
        if not k.upper().startswith(_ACCEL_ENV_PREFIXES)
    }
    if n_devices is not None:
        out["JAX_PLATFORMS"] = "cpu"
        out["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    return out
