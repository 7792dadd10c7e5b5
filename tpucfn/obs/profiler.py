"""Profiling hooks.

The reference exposed no profiling story at all (delegated to nvprof/
framework profilers, undocumented — SURVEY.md §5). tpucfn makes a step-
range trace a flag on every example: traces capture XLA op timelines
*and* ICI collective overlap, viewable in TensorBoard/XProf.
"""

from __future__ import annotations

import contextlib
import itertools
import math
import os
import threading
import time
from pathlib import Path

# jax is imported lazily at the trace/config call sites: this module's
# CompileCacheProbe and ProfileCapture plumbing also run on the jax-free
# planes (obs server routes, `tpucfn check`), where a top-level import
# would drag the whole runtime in.


def start_profiler_server(port: int = 9012):
    """Start the per-host profiler server so XProf/TensorBoard can attach
    a live capture to any host in the fleet.  The examples call this when
    ``--profile-server PORT`` is set (examples/common.py); standalone user
    scripts can call it directly.  Idempotent per process for the same
    port; a second call with a different port raises (jax allows one
    profiler server per process, so silently returning the old one would
    leave the requested port unreachable)."""
    prev = getattr(start_profiler_server, "_port", None)
    if prev is not None:
        if prev != port:
            raise ValueError(
                f"profiler server already running on port {prev}; cannot "
                f"start another on {port} (one per process)")
        return start_profiler_server._server
    import jax

    start_profiler_server._server = jax.profiler.start_server(port)
    start_profiler_server._port = port
    return start_profiler_server._server


def enable_compile_cache(cache_dir: str | None = None,
                         min_compile_time_s: float | None = None) -> str:
    """Turn on XLA's persistent compilation cache and return its
    directory (the rule is ``tpucfn.utils.env.xla_cache_dir``).  With
    ``$JAX_COMPILATION_CACHE_DIR`` set, jax already reads that directory
    and this sets no other; unset, the cache is the fixed
    ``<checkout>/.cache/xla``.  An explicit ``cache_dir`` wins — for
    tests and drills that measure cold against warm.  A relaunch of the
    same program — the restart supervisor's resume, or the second
    ``tpucfn launch`` on a pod — then skips recompilation, which is what
    keeps time_to_first_step from being compile-dominated (SURVEY.md §7.4
    item 6, BASELINE.md metric 2).  Safe to call multiple times.

    ``min_compile_time_s`` (or ``$TPUCFN_XLA_CACHE_MIN_S``) overrides
    the persistence threshold — the ft drills and compile bench pin
    warm-restart accounting on programs that compile in well under the
    production default of 1 s."""
    import os

    import jax

    from tpucfn.utils.env import xla_cache_dir

    explicit = bool(cache_dir)
    cache_dir = cache_dir or xla_cache_dir()
    if explicit or not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if min_compile_time_s is None:
        raw = os.environ.get("TPUCFN_XLA_CACHE_MIN_S", "").strip()
        try:
            min_compile_time_s = float(raw) if raw else 1.0
        except ValueError:
            min_compile_time_s = 1.0
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_s))
    return cache_dir


class ProfilerBusy(RuntimeError):
    """A capture is already running (jax allows one active trace per
    process); the obs server maps this to HTTP 409."""


class ProfileCapture:
    """On-demand profiler capture behind ``POST /profile`` (ISSUE 6).

    Each call traces everything the process does for ``seconds`` into a
    fresh numbered subdirectory of ``log_dir`` and — when a ``tracer``
    is attached — records a ``profile_capture`` span whose attrs link
    the artifact path into the merged ``tpucfn obs`` timeline (the
    operator sees *when* the capture ran relative to steps/incidents,
    and where the XProf trace landed).

    One capture at a time: jax owns a single global trace, so a second
    concurrent request raises :class:`ProfilerBusy` instead of silently
    corrupting the first capture.  ``capture_fn`` is injectable (tests
    swap the real ``jax.profiler`` start/stop for a recorder).
    """

    MAX_SECONDS = 600.0

    def __init__(self, log_dir: str | Path, *, tracer=None,
                 capture_fn=None, sleep=time.sleep):
        self.log_dir = Path(log_dir)
        self.tracer = tracer
        self.sleep = sleep
        self._capture_fn = capture_fn
        self._lock = threading.Lock()
        self._n = itertools.count(1)

    def _capture(self, d: Path, seconds: float) -> None:
        if self._capture_fn is not None:
            self._capture_fn(d, seconds)
            return
        with trace_to(d):
            self.sleep(seconds)

    def __call__(self, seconds: float) -> dict:
        if not math.isfinite(seconds) or not 0 < seconds <= self.MAX_SECONDS:
            raise ValueError(
                f"seconds must be in (0, {self.MAX_SECONDS:g}], "
                f"got {seconds}")
        if not self._lock.acquire(blocking=False):
            raise ProfilerBusy("a profiler capture is already running")
        try:
            d = self.log_dir / f"capture-{os.getpid()}-{next(self._n):03d}"
            d.mkdir(parents=True, exist_ok=True)
            t0 = time.monotonic()
            self._capture(d, seconds)
            t1 = time.monotonic()
            if self.tracer is not None:
                self.tracer.record("profile_capture", start=t0, end=t1,
                                   artifact=str(d), seconds=seconds)
            return {"artifact": str(d), "seconds": seconds,
                    "dur_s": round(t1 - t0, 4)}
        finally:
            self._lock.release()


class CompileCacheProbe:
    """Did the first step's XLA compile come from the persistent cache?

    The goodput ledger charges the whole first step of each incarnation
    to ``compile``; a warm restart (persistent cache hit via
    :func:`enable_compile_cache`) pays deserialization + warmup instead
    of a real compile, and lumping the two inflates the bucket (ISSUE 6
    satellite).  The signal is the cache directory itself, observed
    over the first step (arm/:meth:`rearm` before, :meth:`hit` after):

    * new entries appeared -> the compiler ran and persisted: **miss**;
    * an existing ``*-atime`` sidecar was rewritten -> jax's cache
      ``get`` unconditionally stamps the access-time file on every
      read, so a served-from-cache load leaves exactly this trace:
      **hit**;
    * neither -> **unknown** — the cache is disabled, the layout has no
      atime sidecars, or the compile ran under the min-compile-time
      persistence threshold (nothing read, nothing written) — charge
      plain ``compile``; no number beats a wrong number.  Notably a
      SHARED non-empty cache dir holding none of this run's programs
      stays unknown, not a phantom hit.

    The fleet artifact plane (ISSUE 13) bypasses jax's persistent
    cache entirely — a fetched AOT executable deserializes without
    touching this directory — so the
    :class:`~tpucfn.compilecache.service.CompileCacheClient` reports
    its verdict explicitly through :meth:`mark`; an explicit mark wins
    over the directory heuristic.  :meth:`outcome` is the three-way
    answer the goodput ledger buckets on: ``"fetch"`` (a fleet peer's
    artifact) / ``"hit"`` (persistent cache or local artifact store) /
    ``"miss"`` (a real compile ran) / None (unknown).
    """

    def __init__(self, cache_dir: str | Path):
        self.cache_dir = Path(cache_dir)
        self._before = self._snapshot()
        self._mark: str | None = None

    def _snapshot(self) -> tuple[int, int]:
        """(entry count, newest ``*-atime`` mtime_ns): persists move
        the first, cache reads move the second."""
        count, atime_ns = 0, 0
        try:
            for p in self.cache_dir.iterdir():
                count += 1
                if p.name.endswith("-atime"):
                    try:
                        atime_ns = max(atime_ns, p.stat().st_mtime_ns)
                    except OSError:
                        continue  # racing eviction
        except OSError:
            pass
        return count, atime_ns

    def rearm(self) -> None:
        """Re-snapshot both signals (and clear any explicit mark).
        TrainerObs calls this at the FIRST step's entry: programs
        compiled (or cache-loaded) between enabling the cache and the
        loop reaching step 1 — checkpoint restore's re-materialize
        copy, eval_shape probes — move them too, and counting those
        against the step would misread every resumed run."""
        self._before = self._snapshot()
        self._mark = None

    def mark(self, outcome: str) -> None:
        """Explicit verdict from the artifact plane, recorded as the
        compile ran: ``"fetch"`` (fleet artifact installed),
        ``"store"`` (local artifact store hit), ``"compile"`` (the
        client compiled for real).  Wins over the directory heuristic
        in :meth:`outcome` — the artifact path never touches the
        persistent-cache dir, so the heuristic cannot see it."""
        self._mark = outcome

    def hit(self) -> bool | None:
        if self._mark is not None:
            return self._mark in ("fetch", "store")
        count, atime_ns = self._snapshot()
        if count > self._before[0]:
            return False
        if atime_ns > self._before[1]:
            return True
        return None

    def outcome(self) -> str | None:
        """``"fetch"`` | ``"hit"`` | ``"miss"`` | None (unknown) — the
        goodput split: fetch → ``compile_fetched``, hit →
        ``compile_cached``, miss/None → ``compile``."""
        if self._mark == "fetch":
            return "fetch"
        if self._mark == "store":
            return "hit"
        if self._mark == "compile":
            return "miss"
        h = self.hit()
        if h is None:
            return None
        return "hit" if h else "miss"


@contextlib.contextmanager
def trace_to(log_dir: str | Path):
    """One profiler trace into ``log_dir``: the one place a capture of this
    program starts (``--profile`` and ``POST /profile``).

    Device events only.  The host's tracer perturbs what it captures on
    the jobs this program runs: on a v5e chip, under JAX's defaults (host
    level 2, Python call tracing), every other ResNet-50 step stalled for
    up to 1.4 s and the device read 77% idle against 5% (PERF.md, PR 24);
    at host level 1 without Python tracing the same job's steps took
    0.21–1.33 s against 0.17 s, the device read 71% idle against 43%, and
    stopping the trace took 194 s (PERF.md, PR 25).  The loop's own phases
    are in its trace file (``obs.trace``), on the host's clock."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def profile_steps(log_dir: str | Path, *, enabled: bool = True):
    """Trace everything inside the context into ``log_dir`` (one trace per
    host). Use around a small steady-state step range, not the whole run —
    the first steps are compilation."""
    if not enabled:
        yield
        return
    d = Path(log_dir)
    d.mkdir(parents=True, exist_ok=True)
    with trace_to(d):
        yield
