"""The run's clock: ``T0`` is taken when the entry imports this module, first
of all, so that ``setup_s`` counts from the start of the process."""

import time

T0 = time.monotonic()
MARKS: list[tuple[str, float]] = []


def mark(name: str, at: float | None = None) -> None:
    """One point of the run's timeline, seconds from process start."""
    at = time.monotonic() if at is None else at
    MARKS.append((name, round(at - T0, 3)))
