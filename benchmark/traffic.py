"""One general generator for every traffic mix.

A mix is a data file ``benchmark/traffic/<name>.json``: the step's shape, the
records (kind, count, sizes) and the input path they take.  Records are made
from ``--seed`` alone, each from a generator of its own keyed by (seed, index),
so the same seed gives the same records whatever the thread that made them,
and every seed gives the same sizes.  The records are also kept in memory, for
the check of the input layer after the window.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import records as kinds
from benchmark.weights import seed32

MAKE_THREADS = 8


def _rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng([seed32(seed), index])


def make_records(mix: dict, model: dict, seed: int) -> list[dict]:
    spec = mix["records"]
    make = kinds.load(spec["kind"]).make
    with ThreadPoolExecutor(MAKE_THREADS) as pool:
        return list(pool.map(lambda i: make(spec, model, _rng(seed, i)),
                             range(spec["count"])))


def input_mismatches(mix: dict, model: dict, records: list[dict],
                     fed: list[dict], seed: int) -> int:
    """How many rows the loop was fed that are not, bit for bit, a record of
    this seed: every row of every batch kept."""
    kind = kinds.load(mix["records"]["kind"])
    known = {r[kind.ROW_KEY].tobytes() for r in records}
    return sum(row.tobytes() not in known
               for b in fed for row in np.asarray(b[kind.ROW_KEY]))
