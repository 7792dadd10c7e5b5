"""What drives a cell's window, one module each, found by the ``driver`` of
the cell's file: ``benchmark/drivers/<driver>.py`` gives ``run(c, a,
devices)``, which sets the cell up, measures for ``a.seconds``, compares what
the timed path produced with the plain reference, and returns the result
line's object with ``compared`` as its last key.  Training cells are driven
by ``train``; a serving cell brings its driver as a new module."""

from __future__ import annotations

from benchmark import by_name


def load(name: str):
    return by_name("drivers", name, "driver")


def device_peak_bytes(device) -> int:
    """The chip's peak: the buffers held (state, batches) and what the running
    programs reserved beside them for their temporaries.  The runtime counts
    the two apart; their peaks add up to what the compiler plans for the step
    (13.64 GB against 13.60 for the dense Mistral step, 11.92 against 11.87
    for the flash one; PERF.md, PR 24)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-len(s) * q // 100) - 1))]
