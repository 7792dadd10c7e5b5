"""Readings for setting a cell's limits, taken on the chip at the cell's own
size: ``python tests/benchmark/read_limits.py --workload <name> --seeds a,b,c
--seconds <s> --extra control,half_batch --out <file.jsonl>``.

Every seed is one run of the cell through the benchmark's own driver, all in
one process (set-up is most of a run).  After each, the named extras take the
program's place on the batches that run was fed: ``control`` is the reference
in fp8, the nearest precision below the bfloat16 the configurations state;
``half_batch`` the reference with half of each batch left out.  Each goes
through the same ``compare.verdict`` under the cell's limits as the program
did, and has to come out not correct.  One row a seed is appended to ``--out``.
The benchmark's own runs never come here.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import clock, compare, drivers, run  # noqa: E402

EXTRAS = {"control": {"mode": "fp8"}, "half_batch": {"fault": "half_batch"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--extra", default="")
    ap.add_argument("--extra-seeds", type=int, default=3,
                    help="the extras are read on this many of the seeds")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)

    from benchmark.reference import train as ref_train

    c = run.load_cell(a.workload, a.rehearse)
    devices = run.find_devices(c["entry"]["chips"], a.rehearse)
    driver = drivers.load(c["cell"]["driver"])
    bad = 0
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        clock.MARKS.clear()
        keep: dict = {}
        one = argparse.Namespace(workload=a.workload, seed=seed, trace=0,
                                 seconds=a.seconds, rehearse=a.rehearse)
        result = driver.run(c, one, devices, keep)
        row = {"workload": a.workload, "seed": seed,
               "program": {"correct": result["correct"],
                           "compared": result["compared"]},
               "metrics": result["metrics"], "timeline": result["timeline"]}
        print(f"seed {seed} program: correct {result['correct']} "
              f"{json.dumps(result['compared'])}", flush=True)
        bad += not result["correct"]
        for name in filter(None, a.extra.split(",")) if n < a.extra_seeds else ():
            other = ref_train.follow(c["config"], seed, keep["batches"],
                                     **EXTRAS[name])
            values = compare.numbers(other, keep["reference"])
            ok, table = compare.verdict(
                values, {k: v for k, v in keep["limits"].items() if k in values})
            for k, v in values.items():
                table.setdefault(k, {"value": v, "limit": None})
            row[name] = {"correct": bool(ok), "compared": table,
                         "worst": compare.worst_leaves(other, keep["reference"])}
            print(f"seed {seed} {name}: correct {ok} {json.dumps(table)}",
                  flush=True)
            bad += bool(ok)   # a control or a fault has to read not correct
        with open(a.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    print(f"out of line: {bad}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
