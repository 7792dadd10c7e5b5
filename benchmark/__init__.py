"""The benchmark: harness, yardstick and plain references.

Everything that decides a number lives here, under ``BENCHMARK.json``'s
``paths``: traffic generation, weights, the reduction from traces and spans
to metrics, the table of peaks, the counting of operations and bytes, the
plain references and the comparison that decides ``correct``.  From the
program (``tpucfn``, ``examples``) it takes the system under test and its
spans, counters and kernel names, nothing else.

Entry: ``python -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``.
"""

import importlib


def by_name(group: str, name: str, what: str):
    """The module ``benchmark/<group>/<name>.py``: whatever belongs to one
    family, record kind, driver or reader is a file of its own, found by the
    name a data file gives, so that a later PR adds and edits nothing."""
    full = f"benchmark.{group}.{name}"
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise ValueError(
            f"unknown {what} {name!r}: no benchmark/{group}/{name}.py") from e
