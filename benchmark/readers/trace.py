"""The reduction from a profiler trace to what the per-layer readers read.

A trace is reduced to one ``DeviceTrace`` per device plane: the events of its
operations line and of its modules line as ``(name, start_s, dur_s)``.  The
traced interval is cut to whole steps: from the start of the first run of the
step's module to the start of its last run, so that every count of steps,
operations and busy seconds is over exactly the same stretch of device time.

Which planes and lines are which was read off a real trace of this program on
a v5e chip (PERF.md section 3): device planes are named ``/device:TPU:<n>``;
their line ``XLA Ops`` holds one event per executed HLO operation, ``XLA
Modules`` one per run of a compiled program.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

NAME_CHARS = 240   # an operation's name is its whole HLO text; its head tells it apart

Event = tuple[str, float, float]


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: list[Event]
    modules: list[Event]


def load(trace_dir) -> list[DeviceTrace]:
    """Every device plane of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        out.append(DeviceTrace(
            plane.name,
            _events(lines.get(OPS_LINE)), _events(lines.get(MODULES_LINE))))
    return out


def _events(line) -> list[Event]:
    if line is None:
        return []
    return sorted(((e.name[:NAME_CHARS], e.start_ns / 1e9, e.duration_ns / 1e9)
                   for e in line.events), key=lambda e: e[1])


def save(devices: list[DeviceTrace], path) -> None:
    """The reduced form, for a recorded sample kept with the tests."""
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.asdict(d) for d in devices], f)


def load_saved(path) -> list[DeviceTrace]:
    with gzip.open(path, "rt") as f:
        return [DeviceTrace(d["name"], [tuple(e) for e in d["ops"]],
                            [tuple(e) for e in d["modules"]])
                for d in json.load(f)]


def step_interval(dev: DeviceTrace, step_module: str, skip: int = 0):
    """(t0, t1, steps): from the first to the last start of the step's
    module, the first ``skip`` runs left out; None where the trace holds
    fewer than two runs of it."""
    starts = [s for n, s, _ in dev.modules if step_module in n][skip:]
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], len(starts) - 1


def clip(events: list[Event], t0: float, t1: float) -> list[Event]:
    out = []
    for n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((n, a, b - a))
    return out


def busy_intervals(events: list[Event]) -> list[tuple[float, float]]:
    """The union of the events' intervals."""
    out: list[list[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def busy_seconds(events: list[Event]) -> float:
    return sum(b - a for a, b in busy_intervals(events))


def idle_gaps(events: list[Event], t0: float, t1: float):
    """(start, end) of every stretch of [t0, t1] in which nothing ran."""
    gaps, at = [], t0
    for a, b in busy_intervals(clip(events, t0, t1)):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def self_seconds(events: list[Event]) -> dict[str, float]:
    """Busy seconds by name: every instant goes to the operation that started
    last among those running (a loop's event spans its body's, and a few
    events overlap without nesting), so the names sum to the busy union."""
    total: dict[str, float] = {}
    active: list[tuple[str, float]] = []   # (name, end), in order of start
    at = 0.0

    def run_until(stop: float):
        nonlocal at
        while active:
            while active and active[-1][1] <= at:
                active.pop()
            if not active or at >= stop:
                return
            name, end = active[-1]
            upto = min(end, stop)
            total[name] = total.get(name, 0.0) + upto - at
            at = upto

    for n, s, d in sorted(events, key=lambda e: e[1]):
        run_until(s)
        at = max(at, s)
        active.append((n, s + d))
    run_until(float("inf"))
    return total
