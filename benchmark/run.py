"""The benchmark's entry: one run of one cell.

``python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``

The cell's files are found by its name in ``BENCHMARK.json``; the driver its
file names (``benchmark/drivers/``) sets it up, measures and compares.  The
last line of standard output is the result.  Without a TPU the run fails and
prints none; ``--rehearse`` (the tests' option) shrinks the cell by its file's
``rehearsal`` entry, runs wherever JAX runs, and reports no device metric.
"""

from __future__ import annotations

from benchmark import clock  # first of all: the run's clock starts here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CONFIG_META = ("family", "source", "job", "published", "assumed", "deployment")


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(name: str, rehearse: bool) -> dict:
    """Everything one cell is, found by name from ``BENCHMARK.json``."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = load_json(ROOT / cfg_entry["file"])
    mix = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    cell = load_json(HERE / "workloads" / f"{name}.json")
    if rehearse:
        small = cell.get("rehearsal", {})
        config = merge(config, small.get("config", {}))
        mix = merge(mix, small.get("traffic", {}))
        cell = merge(cell, small.get("cell", {}))
    # the sizes are the file's top-level keys, under their published names
    config["model"] = {k: v for k, v in config.items() if k not in CONFIG_META}
    return {"manifest": manifest, "entry": entry, "config": config,
            "mix": mix, "cell": cell}


def find_devices(chips: int, rehearse: bool):
    import jax

    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"device(s) of platform {devices[0].platform!r}")
    return devices[:chips]


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: shrunk sizes, any platform, no device metric")
    return ap.parse_args(argv)


def report(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    for k, v in result["compared"].items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    from benchmark import drivers

    a = parse(argv)
    c = load_cell(a.workload, a.rehearse)
    devices = find_devices(c["entry"]["chips"], a.rehearse)
    report(drivers.load(c["cell"]["driver"]).run(c, a, devices))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
