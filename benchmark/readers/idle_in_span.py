"""The device's idle time, put down to the phase the host's loop was in.

The trace is on the profiler's clock and the program's spans on the host's
``time.monotonic()``.  The offset between them, theta (host minus trace), is
fitted over every step of the traced interval.  Host step k (a ``step`` span
and its ``step_wait`` child, on the loop's thread) is paired with run k -
shift of the step's module on the device, and causality bounds theta on both
sides: a run cannot start before the host began to launch it,

    theta >= max_k(step.start - run.start),

and the host cannot wake before the run it waited for has ended,

    theta <= min_k(step_wait.end - run.end).

The pairing is the shift that leaves this window non-empty; where several do
(the steps are then periodic to within the window, and every such shift puts
the idle time into the same phases) the one whose theta lies nearest the
host's reading at the profiler's start, ``ctx.host_interval[0]``; where none
does, no number.

theta is taken ``WAKE_S`` below the window's upper end (and not below its
lower end).  The wake-up after a run's end is the steadiest lag there is, but
not a short one: the host fetches the step counter from the device before it
wakes.  Against the profile's own start (the trace's ``profile_start_time``,
which the reduction in ``trace.py`` does not keep, and which agreed with the
profiler's host events to 3 us) the least wake-up lag of a trace read 2.29 and
2.35 ms in ``rn50-cached`` and 1.65 and 1.67 ms in ``mistral7b-s8192``, on a
TPU v5 lite (PERF.md, PR 25); within one trace it varies by 0.2-0.3 ms.  The
lag from launch to start is of no such use: it holds the wait for the batch's
bytes, 31-41 ms a step in ``rn50-cached``.

Every idle interval of the device inside the cut is then moved onto the
host's clock and divided among the leaf spans of the loop's thread (the spans
of that thread that no other of its spans names as parent) that were open at
the time.  ``span`` asks for the part inside the spans of that name,
``outside`` for the part inside none of the named ones; the value is that
time over the cut's length, in per cent, the mean over the chips, so that the
phases of a cell sum to its ``device_idle_share``.  A program that writes no
``step_wait`` span gives nothing to read.
"""

from __future__ import annotations

import bisect
import dataclasses
import sys

from benchmark.readers import trace

WAKE_S = 0.002   # the least lag from a run's end to the host's waking


@dataclasses.dataclass
class Fit:
    theta: float                    # host clock minus trace clock, seconds
    lo: float                       # the window the chosen shift leaves
    hi: float
    shift: int                      # host step k is device run k - shift
    shifts: list[int]               # every shift that leaves a window


def loop_thread(spans: list[dict]):
    """(steps, leaves) of the thread that runs the loop: its steps as
    ``(launch, woke)`` in order, and its leaf spans as ``(start, end, name)``
    in order; None where no step was divided."""
    woke = {s["parent_id"]: s["start"] + s["dur_s"] for s in spans
            if s["name"] == "step_wait"}
    split = [s for s in spans if s["name"] == "step" and s["span_id"] in woke]
    if not split:
        return None
    mine = [s for s in spans if s.get("tid") == split[0].get("tid")]
    parents = {s["parent_id"] for s in mine}
    leaves = sorted((s["start"], s["start"] + s["dur_s"], s["name"])
                    for s in mine if s["span_id"] not in parents)
    return sorted((s["start"], woke[s["span_id"]]) for s in split), leaves


def fit(steps, runs, near: float) -> Fit | None:
    """``steps``: the host's ``(launch, woke)``; ``runs``: the device's
    ``(start, end)`` of the step's module, every one of them some step."""
    if not runs:
        return None
    found = []
    for shift in range(len(steps) - len(runs) + 1):
        pairs = list(zip(steps[shift:], runs))
        lo = max(h[0] - r[0] for h, r in pairs)
        hi = min(h[1] - r[1] for h, r in pairs)
        if lo <= hi:
            theta = max(lo, hi - WAKE_S)
            found.append((abs(theta - near), shift, theta, lo, hi))
    if not found:
        return None
    _, shift, theta, lo, hi = min(found)
    return Fit(theta, lo, hi, shift, sorted(f[1] for f in found))


def fit_device(ctx, dev, steps) -> Fit | None:
    runs = [(s, s + d) for n, s, d in dev.modules if ctx.step_module in n]
    return fit(steps, runs, ctx.host_interval[0])


def divide(gaps, leaves) -> dict[str, float]:
    """Seconds of ``gaps`` (host clock, in order) inside the leaf spans, by
    the spans' name.  One thread's leaves do not overlap, so every instant
    goes to one span at the most."""
    ends = [e for _, e, _ in leaves]
    out: dict[str, float] = {}
    for a, b in gaps:
        i = bisect.bisect_right(ends, a)
        while i < len(leaves) and leaves[i][0] < b:
            s, e, name = leaves[i]
            got = min(e, b) - max(s, a)
            if got > 0:
                out[name] = out.get(name, 0.0) + got
            i += 1
    return out


def read(ctx, span: str | None = None, outside: list[str] | None = None):
    cuts = ctx.cut()
    if cuts is None or ctx.host_interval is None:
        return None
    loop = loop_thread(ctx.spans)
    if loop is None:
        return None
    steps, leaves = loop
    shares = []
    for dev, (t0, t1, _) in zip(ctx.devices, cuts):
        f = fit_device(ctx, dev, steps)
        if f is None:
            print(f"idle_in_span: no pairing of the host's {len(steps)} steps "
                  f"with the runs of {ctx.step_module!r} on {dev.name} leaves "
                  "a window for the clocks' offset: no number",
                  file=sys.stderr)
            return None
        gaps = [(a + f.theta, b + f.theta)
                for a, b in trace.idle_gaps(dev.ops, t0, t1)]
        inside = divide(gaps, leaves)
        if span is not None:
            got = inside.get(span, 0.0)
        else:
            got = sum(b - a for a, b in gaps) - sum(
                inside.get(n, 0.0) for n in outside or ())
        shares.append(got / (t1 - t0))
    return 100.0 * sum(shares) / len(shares)
