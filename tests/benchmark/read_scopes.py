"""Device time by the program's own parts, taken on the chip at a cell's own
size: ``python tests/benchmark/read_scopes.py --workload <name> --seed <n>
--seconds <s> --out <dir>``.

One traced run of the cell through the benchmark's own driver, then the join
that ``benchmark/readers/scope_time_share.py`` makes (the program's
``step_program`` span with the trace, by instruction name), whole: what
builders wrote by hand into ``chiprun_out/pr3*/ops_*.json``.  Printed as one
JSON line and kept as ``<out>/scopes.json``, beside what it was worked out
from (``spans.jsonl``: the program's spans; ``devices.json.gz``: the reduced
trace), so that a second look needs no second run:

- ``passes``: busy seconds and share by pass, the unmapped events beside them
  (they sum to busy time);
- ``table``: seconds and share by pass x the first ``--levels`` levels of
  scope;
- ``classes``: the twenty longest classes ``[pass, scope, root, product]``,
  the tail summed;
- ``unmapped``: the longest events whose instruction the map does not hold;
- ``kernels``: the pass and scope of every Pallas kernel the trace shows
  (``flash_fwd`` under ``forward`` or ``remat``, ``flash_dkv`` under
  ``backward``);
- ``program``: the span's own account (its three children, the line's bytes,
  the planned memory, where the compile came from);
- the run's per-layer metrics.

``--sample <file>`` also writes what the tests keep: a few steps of the
reduced trace, names cut short, and the run's ``step_program`` line.  The
benchmark's own runs never come here.
"""

import argparse
import collections
import dataclasses
import gzip
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import drivers, readers, run  # noqa: E402
from benchmark.readers import scope_time_share, trace  # noqa: E402

SAMPLE_STEPS = 3
SAMPLE_NAME_CHARS = 64


def share(seconds: float, busy: float) -> dict:
    return {"s": seconds, "share": 100.0 * seconds / busy}


def table(ctx, levels: int = 3) -> dict | None:
    """The whole join over a reader's context; None without span or cut."""
    found = scope_time_share.events(ctx)
    if found is None:
        return None
    events, classes = found
    seconds: dict = collections.Counter()
    unmapped: dict = collections.Counter()
    kernels: dict = {}
    for name, i, s in events:
        seconds[i] += s
        if i is None:
            unmapped[name[:80]] += s
        elif "custom-call(" in name:
            kernel = re.sub(r"[.0-9]+$", "", name[1:name.index(" ")])
            k = kernels.setdefault(kernel, {})
            key = f"{classes[i][0]} {classes[i][1]}"
            k[key] = k.get(key, 0.0) + s
    busy = sum(seconds.values())
    passes: dict = collections.Counter()
    rows: dict = collections.Counter()
    for i, s in seconds.items():
        p, scope = ("unmapped", "") if i is None else classes[i][:2]
        passes[p] += s
        rows[(p, "/".join(scope.split("/")[:levels]))] += s
    longest = sorted(((s, classes[i]) for i, s in seconds.items()
                      if i is not None), key=lambda r: -r[0])
    return {
        "busy_s": busy,
        "passes": {p: share(s, busy) for p, s in sorted(passes.items())},
        "table": [[p, scope, s, 100.0 * s / busy] for (p, scope), s in
                  sorted(rows.items(), key=lambda r: -r[1])],
        "classes": [[*cls, s, 100.0 * s / busy] for s, cls in longest[:20]],
        "classes_tail": share(sum(s for s, _ in longest[20:]), busy),
        "unmapped": [[n, s] for n, s in unmapped.most_common(10)],
        "kernels": kernels}


def program(spans) -> list[dict]:
    """Every ``step_program`` span's own account, the map left out."""
    out = []
    for s in spans:
        if s["name"] != "step_program":
            continue
        kids = {k["name"]: k["dur_s"] for k in spans
                if k.get("parent_id") == s["span_id"]
                and k["name"].startswith("program_")}
        out.append({"trace_id": s["trace_id"], "dur_s": s["dur_s"],
                    "line_bytes": len(json.dumps(s)), **kids,
                    "classes": len(s["attrs"]["classes"]),
                    **{k: v for k, v in s["attrs"].items()
                       if k not in ("classes", "ops")}})
    return out


def sample(ctx, path) -> None:
    """A few whole steps of the first device's cut, names cut short, and the
    ``step_program`` line they ran: what ``test_bm_scopes.py`` reads."""
    dev = ctx.devices[0]
    runs = [m for m in dev.modules if ctx.step_module in m[0]]
    keep = runs[ctx.skip_steps:ctx.skip_steps + SAMPLE_STEPS + 1]
    t0, t1 = keep[0][1], keep[-1][1] + keep[-1][2]
    small = trace.DeviceTrace(
        dev.name,
        [(n[:SAMPLE_NAME_CHARS], s, d) for n, s, d in dev.ops
         if s + d > t0 and s < t1],
        keep)
    with gzip.open(path, "wt") as f:
        json.dump({"devices": [dataclasses.asdict(small)],
                   "span": scope_time_share.program(ctx),
                   "host_interval": list(ctx.host_interval),
                   "step_module": ctx.step_module}, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--levels", type=int, default=3)
    ap.add_argument("--sample")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)

    c = run.load_cell(a.workload, a.rehearse)
    devices = run.find_devices(c["entry"]["chips"], a.rehearse)
    seen: dict = {}
    per_layer = readers.per_layer

    def keeping(c, window, run_dir, trace_dir, devs, rehearse):
        """What the readers are about to read, kept before the driver
        removes it."""
        seen["spans"] = readers.program_spans(run_dir)
        seen["host_interval"] = window.traced
        (out / "spans.jsonl").write_text(
            "".join(json.dumps(s) + "\n" for s in seen["spans"]))
        (out / "host.json").write_text(json.dumps(
            {"host_interval": window.traced}))
        if not rehearse:
            seen["devices"] = trace.load(trace_dir)
            trace.save(seen["devices"], out / "devices.json.gz")
        return per_layer(c, window, run_dir, trace_dir, devs, rehearse)

    readers.per_layer = keeping
    one = argparse.Namespace(workload=a.workload, seed=a.seed, trace=1,
                             seconds=a.seconds, rehearse=a.rehearse)
    try:
        result = drivers.load(c["cell"]["driver"]).run(c, one, devices)
    finally:
        readers.per_layer = per_layer

    summary = {"workload": a.workload, "seed": a.seed,
               "correct": result["correct"], "device": result["device"],
               "metrics": result["metrics"],
               "program": program(seen["spans"])}
    if not a.rehearse:
        ctx = readers.Context(
            config=c["config"], mix=c["mix"], chips=len(devices),
            spans=seen["spans"], host_interval=seen["host_interval"],
            devices=seen["devices"],
            step_module=c["cell"]["loop"]["step_module"], peak=None,
            skip_steps=1)
        summary.update(table(ctx, a.levels) or {})
        if a.sample and scope_time_share.program(ctx) and ctx.cut():
            sample(ctx, a.sample)
    (out / "scopes.json").write_text(json.dumps(summary))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
