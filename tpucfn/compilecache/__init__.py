"""Fleet-wide warm start: content-addressed XLA artifact cache +
distribution plane (ISSUE 13 tentpole).

Time-to-first-step is compile-dominated (45.8 s of 64 s on the bench
row), and every ft relaunch, adopted-coordinator recovery, and serve
replica spin-up repays the same compile.  PR 6 proved the single-host
half (jax's persistent compile cache); this package is the fleet half:

* :mod:`~tpucfn.compilecache.store` — a jax-free content-addressed
  local store of serialized compiled executables, keyed by a digest
  computed *before* compiling (StableHLO hash + avals + shardings +
  mesh + device_kind + jax version), with checksummed payloads that are
  refused loudly and quarantined on corruption (the PR 7
  ckpt-quarantine lesson — never silently recompiled into a wrong-key
  slot).
* :mod:`~tpucfn.compilecache.service` — a jax-free artifact server
  (host 0, an input-role host, or the launch coordinator) speaking the
  PR 11 length-prefixed framing, with a handshake that refuses
  device_kind/jax-version mismatches and a single-flight claim
  protocol so a cold fleet compiles each program exactly once.
* :mod:`~tpucfn.compilecache.jit` — the jax glue: ``maybe_warm`` wraps
  a ``jax.jit`` callable so its first call per avals-signature goes
  lower → key → local-store / fleet-fetch / compile+publish, returning
  the AOT ``deserialize_and_load``-ed executable on a hit.  With no
  client configured (``TPUCFN_COMPILE_CACHE_ADDRS`` and
  ``TPUCFN_COMPILE_CACHE_DIR`` unset) it returns the jitted callable
  itself — byte-identical behavior, pinned by test.

The goodput ledger splits the first step's charge three ways —
``compile`` (a real XLA compile ran), ``compile_cached`` (jax's
persistent cache or the local artifact store served it), and
``compile_fetched`` (a fleet peer's artifact was fetched) — from the
``outcome`` the trainer's ``step_program`` span carries.
"""

from tpucfn.compilecache.store import (  # noqa: F401
    ArtifactStore,
    CacheCorrupt,
    CacheMismatch,
    cache_key,
    default_store_dir,
)
from tpucfn.compilecache.service import (  # noqa: F401
    ArtifactClient,
    ArtifactServer,
    CompileCacheClient,
    cache_addrs_from_env,
    COMPILE_CACHE_ADDRS_ENV,
    COMPILE_CACHE_DIR_ENV,
)


def configure_from_env(*, tracer=None, registry=None, env=None):
    """Build and install the process-default compile-cache client from
    the launcher's env fan-out.  Returns the client, or None when
    neither ``TPUCFN_COMPILE_CACHE_ADDRS`` nor
    ``TPUCFN_COMPILE_CACHE_DIR`` is set (the pinned byte-identical
    default) — that no-op path never touches jax.  When a cache IS
    configured, the runtime-identity probe (device_kind, versions —
    two key components and the handshake identity) imports jax HERE:
    only call this from processes that run jitted programs, never from
    the jax-free planes (input hosts, the artifact server, the
    coordinator)."""
    from tpucfn.compilecache.jit import configure_client_from_env

    return configure_client_from_env(tracer=tracer, registry=registry,
                                     env=env)
