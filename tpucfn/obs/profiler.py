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
# ProfileCapture plumbing also runs on the jax-free
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
