"""Readings for the margin of a cell whose driver compares a choice of keys
(``benchmark/drivers/train_dsa.py``), taken on the chip at the cell's own
size: ``python tests/benchmark/read_choice.py --workload <name> --seeds a,b,c
--seconds <s> --trace-seeds <n> --control-seeds <m> --out <file.jsonl>``.

Every seed is one run of the cell through its driver, all in one process, the
first ``n`` traced (their per-layer metrics in the row).  On the first ``m``
seeds the driver's ``select_control`` then takes the program's place: the
reference's own choice from fp8 operands goes through the same
``compare.verdict`` under the cell's ``select_mismatch`` limit, and has to
come out not correct; its ``select_gap`` is the reading the margin is set
below.  One row a seed is appended to ``--out``.  The benchmark's own runs
never come here.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import clock, compare, drivers, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace-seeds", type=int, default=0)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)

    c = run.load_cell(a.workload, a.rehearse)
    devices = run.find_devices(c["entry"]["chips"], a.rehearse)
    driver = drivers.load(c["cell"]["driver"])
    bad = 0
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        clock.MARKS.clear()
        keep: dict = {}
        one = argparse.Namespace(workload=a.workload, seed=seed,
                                 trace=int(n < a.trace_seeds),
                                 seconds=a.seconds, rehearse=a.rehearse)
        result = driver.run(c, one, devices, keep)
        row = {"workload": a.workload, "seed": seed,
               "program": {"correct": result["correct"],
                           "compared": result["compared"]},
               "metrics": result["metrics"], "device": result["device"],
               "timeline": result["timeline"]}
        print(f"seed {seed} program: correct {result['correct']} "
              f"{json.dumps(result['compared'])}", flush=True)
        bad += not result["correct"]
        if n < a.control_seeds:
            values = driver.select_control(c, seed, keep["batches"])
            ok, table = compare.verdict(values, {
                k: v for k, v in keep["limits"].items() if k in values})
            for k, v in values.items():
                table.setdefault(k, {"value": v, "limit": None})
            row["select_control"] = {"correct": bool(ok), "compared": table}
            print(f"seed {seed} select_control: correct {ok} "
                  f"{json.dumps(table)}", flush=True)
            bad += bool(ok)   # the control has to read not correct
        with open(a.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    print(f"out of line: {bad}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
