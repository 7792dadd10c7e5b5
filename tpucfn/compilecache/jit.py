"""jax glue for the artifact cache: fingerprint → fetch-or-compile.

``maybe_warm(jitted, label=...)`` is the integration point the serve
engine, the RL plane and the trainer's init use: it wraps a ``jax.jit``
callable in a :class:`WarmJit` so the first call per avals-signature runs

    lower (cheap) → cache key (BEFORE compiling — a hit skips the
    compile entirely) → local store / fleet fetch / single-flight
    compile+publish → AOT executable

and subsequent calls go straight to the compiled executable.  With no
client configured it returns the jitted callable itself — the pinned
byte-identical default.  The trainer's step and eval programs are always a
:class:`WarmJit` (``Trainer._program``): with no client that is lower →
compile → call the ``Compiled``, the same one path without the fetch, so
that the program can time its compile and say what it compiled
(``obs.program``).

Serialization uses jax's AOT export surface
(``jax.experimental.serialize_executable.serialize`` /
``deserialize_and_load`` — the PAPERS.md whole-program-AOT direction):
the artifact IS the loaded executable, so a hit pays deserialization,
never XLA.  Any failure anywhere in the warm path permanently falls
back to the plain jitted callable for that wrapper — same program,
bit-identical trajectory, just without the warm start.

TRUST MODEL: jax's AOT surface is pickle-based, so deserializing an
artifact EXECUTES whatever the payload encodes — the sha256 checks
prove integrity (the bytes arrived as published), not authenticity
(who published them).  The artifact plane therefore carries the same
trust boundary as the rest of the launch fan-out (the input plane, the
heartbeat dir, the run storage): server and store dirs must live on
the cluster's private network / filesystem, reachable only by fleet
members.  Do not point ``TPUCFN_COMPILE_CACHE_ADDRS`` at an untrusted
server or ``TPUCFN_COMPILE_CACHE_DIR`` at a world-writable path on a
shared machine.
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from typing import Any, Callable

from tpucfn.compilecache.service import (
    CompileCacheClient,
    cache_addrs_from_env,
    COMPILE_CACHE_DIR_ENV,
)
from tpucfn.compilecache.store import ArtifactStore, cache_key


# -- process-default client -------------------------------------------------

_default_client: CompileCacheClient | None = None
_default_lock = threading.Lock()


def set_default_client(client: CompileCacheClient | None) -> None:
    global _default_client
    with _default_lock:
        _default_client = client


def get_default_client() -> CompileCacheClient | None:
    return _default_client


def runtime_identity() -> tuple[str, str]:
    """(device_kind, jax_version) of this process — two of the key
    components, and the handshake identity."""
    import jax

    try:
        kind = jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — no backend yet: identity is versions
        kind = "unknown"
    import jaxlib

    return kind, f"{jax.__version__}/{getattr(jaxlib, '__version__', '?')}"


def configure_client_from_env(*, tracer=None, registry=None,
                              env=None) -> CompileCacheClient | None:
    """Install the process-default client per the launcher fan-out.
    ``TPUCFN_COMPILE_CACHE_ADDRS`` and/or ``TPUCFN_COMPILE_CACHE_DIR``
    unset → None, nothing installed, ``maybe_warm`` stays an identity
    function (byte-identical behavior, pinned)."""
    import os

    e = os.environ if env is None else env
    addrs = cache_addrs_from_env(e)
    store_dir = (e.get(COMPILE_CACHE_DIR_ENV) or "").strip()
    if not addrs and not store_dir:
        return None
    if not store_dir:
        from tpucfn.compilecache.store import default_store_dir

        store_dir = default_store_dir()
    device_kind, jax_version = runtime_identity()
    store = ArtifactStore(store_dir, device_kind=device_kind,
                          jax_version=jax_version)
    client = CompileCacheClient(
        store, addrs, device_kind=device_kind, jax_version=jax_version,
        registry=registry, tracer=tracer)
    set_default_client(client)
    return client


# -- fingerprinting ---------------------------------------------------------

def _config_fingerprint() -> dict:
    """The jax.config flags that change compiled code.  Anything that
    alters lowering shows up in the StableHLO hash already; these are
    the compile-time knobs that do not."""
    import jax

    out = {}
    for flag in ("jax_enable_x64", "jax_default_matmul_precision",
                 "jax_threefry_partitionable", "jax_debug_nans",
                 "jax_disable_jit"):
        try:
            out[flag] = repr(getattr(jax.config, flag))
        except AttributeError:
            continue
    return out


def lowered_fingerprint(lowered, *, label: str = "") -> str:
    """The content-addressed key of one lowered-but-not-compiled
    program.  Computed pre-compile: StableHLO text hash (covers avals,
    shardings, donation, and the computation itself), mesh/backend
    identity, jax + jaxlib versions, and compile-relevant config."""
    hlo = lowered.as_text()
    device_kind, jax_version = runtime_identity()
    import jax

    components = {
        "hlo_sha256": hashlib.sha256(hlo.encode()).hexdigest(),
        "device_kind": device_kind,
        "versions": jax_version,
        "backend": jax.default_backend(),
        "num_devices": jax.device_count(),
        "config": _config_fingerprint(),
        "label": label,
    }
    return cache_key(components)


# -- AOT (de)serialization --------------------------------------------------

def serialize_compiled(compiled) -> bytes | None:
    """One self-describing payload for a ``Compiled`` executable, or
    None when this backend/jax build cannot serialize (the caller then
    simply skips publishing).  The payload names the devices the program
    was compiled for: loading must put it back on exactly those."""
    import pickle

    from jax.experimental.serialize_executable import serialize

    payload, in_tree, out_tree = serialize(compiled)
    devices = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps({"v": 2, "exe": payload, "devices": devices,
                         "in_tree": in_tree, "out_tree": out_tree})


def deserialize_compiled(payload: bytes, meta: dict):
    import pickle

    import jax
    from jax.experimental.serialize_executable import deserialize_and_load

    obj = pickle.loads(payload)
    if not isinstance(obj, dict) or obj.get("v") != 2:
        raise ValueError("unknown compile-cache payload format")
    # Without execution_devices jax loads across every local device and a
    # one-device program then fails at call time on a several-device host.
    by_id = {d.id: d for d in jax.local_devices()}
    return deserialize_and_load(
        obj["exe"], obj["in_tree"], obj["out_tree"],
        execution_devices=[by_id[i] for i in obj["devices"]])


# -- the wrapper ------------------------------------------------------------

def _avals_signature(args: tuple, kwargs: dict) -> tuple:
    """Hashable (shape, dtype, weak type) tree signature of one call — what
    keys the per-wrapper executable memo (bucketed serve prefills get one
    entry per bucket, the trainer exactly one)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef,
            tuple((getattr(x, "shape", None),
                   str(getattr(x, "dtype", type(x).__name__)),
                   bool(getattr(x, "weak_type", False)))
                  for x in leaves))


class WarmJit:
    """One ``jax.jit`` result, lowered and compiled explicitly: each new
    avals-signature is lowered at the call's arguments, compiled — through
    the artifact cache where a ``client`` is given, by the compiler (and
    JAX's persistent cache) where it is None — and the ``Compiled`` is
    called from then on.  ``on_program(compiled, label=, outcome=,
    lower_start=, compile_start=, compile_end=)`` hears of every program:
    ``outcome`` is ``fetch`` (a fleet peer's artifact), ``hit`` (the local
    artifact store, or JAX's persistent cache) or ``miss`` (the compiler
    ran).  Thread-safe.  A failure of the artifact plane disables the
    wrapper (plain jit from then on) — degradation is always to the exact
    same program; with no client there is no plane to fail, and what the
    compiler raises reaches the caller."""

    def __init__(self, jitted, client: CompileCacheClient | None = None, *,
                 label: str = "", on_program: Callable | None = None):
        self._jit = jitted
        self.client = client
        self.label = label
        self.on_program = on_program
        self._compiled: dict[tuple, Any] = {}
        # Steady-state fast path: while exactly ONE shape bucket exists
        # (the trainer's every-step case), dispatch straight to its
        # executable — the per-call tree_flatten signature walk is paid
        # only while buckets are still being discovered.  An AOT
        # executable validates input avals BEFORE running (donation
        # included), raising TypeError on a new bucket, which routes
        # back through the slow path.
        self._fast: Any = None
        self._lock = threading.Lock()
        self._disabled = False

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        """Resolved-executable count, the duck-type the
        ``jit_cache_programs`` gauge reads (obs.metrics ``jit_sources``):
        the buckets in ``_compiled``, plus whatever the underlying
        jit compiled itself on the degraded path."""
        try:
            n = int(self._jit._cache_size())
        except Exception:  # noqa: BLE001 — gauge is best-effort
            n = 0
        return n + len(self._compiled)

    def _build(self, args, kwargs):
        from tpucfn.obs.program import CacheVerdict

        lower_start = time.monotonic()
        lowered = self._jit.lower(*args, **kwargs)
        compile_start = time.monotonic()
        with CacheVerdict() as verdict:
            if self.client is None:
                compiled = lowered.compile()
                outcome = verdict.outcome
            else:
                compiled, fleet = self.client.get_or_compile(
                    lowered_fingerprint(lowered, label=self.label),
                    lowered.compile,
                    serialize_fn=_serialize_or_none,
                    deserialize_fn=deserialize_compiled,
                    label=self.label)
                outcome = {"fetch": "fetch", "store": "hit"}.get(
                    fleet, verdict.outcome)
        if self.on_program is not None:
            self.on_program(compiled, label=self.label, outcome=outcome,
                            lower_start=lower_start,
                            compile_start=compile_start,
                            compile_end=time.monotonic())
        return compiled

    def __call__(self, *args, **kwargs):
        if self._disabled:
            return self._jit(*args, **kwargs)
        fast = self._fast
        if fast is not None:
            try:
                return fast(*args, **kwargs)
            except TypeError:
                # different avals than the known bucket: this wrapper is
                # multi-bucket (or the caller erred) — drop the fast
                # path for good, the signature walk handles both.
                self._fast = None
        try:
            sig = _avals_signature(args, kwargs)
        except Exception:  # noqa: BLE001 — unhashable call shape
            self._disabled = True
            return self._jit(*args, **kwargs)
        compiled = self._compiled.get(sig)
        if compiled is None:
            with self._lock:
                compiled = self._compiled.get(sig)
                if compiled is None:
                    try:
                        compiled = self._build(args, kwargs)
                    except Exception:  # noqa: BLE001 — degrade, bit-identical
                        if self.client is None:
                            raise
                        self._disabled = True
                        return self._jit(*args, **kwargs)
                    self._compiled[sig] = compiled
                self._fast = (compiled if len(self._compiled) == 1
                              else None)
        return compiled(*args, **kwargs)


def _serialize_or_none(compiled) -> bytes | None:
    try:
        return serialize_compiled(compiled)
    except Exception:  # noqa: BLE001 — backend cannot serialize: no publish
        return None


def maybe_warm(jitted, *, label: str = "",
               client: CompileCacheClient | None = None):
    """The one integration point: wrap ``jitted`` in the artifact-cache
    warm path when a client is configured, return it UNCHANGED when not
    (``TPUCFN_COMPILE_CACHE_ADDRS``/``_DIR`` absent ⇒ byte-identical
    behavior — pinned by test_compilecache)."""
    c = client if client is not None else get_default_client()
    if c is None:
        return jitted
    return WarmJit(jitted, c, label=label)
