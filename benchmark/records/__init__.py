"""Record kinds, one module each, found by ``records.kind`` of a traffic
mix: ``benchmark/records/<kind>.py`` gives ``make(spec, model, rng)`` (one
record from its own generator) and ``ROW_KEY`` (the field whose rows the
check of the input layer compares bit for bit): the mixes here pass through
no transform of the program's on the way to the loop."""

from benchmark import by_name


def load(kind: str):
    return by_name("records", kind, "record kind")
