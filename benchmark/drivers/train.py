"""One run of a training cell: the window drives the program's own loop,
``examples/common.run_train_loop``.

Set-up (records, shards, seeded state, compile, warm-up steps), the window,
then, with the program's state freed, the reference and the comparison.
"""

from __future__ import annotations

import contextlib
import gc
import inspect
import os
import shutil
import sys
from pathlib import Path

from benchmark import clock, drivers

ROOT = Path(__file__).resolve().parents[2]


def end_to_end(c: dict, window, setup_s: float, items: int, chips: int) -> dict:
    names = {m["name"]: m for m in c["manifest"]["end_to_end"]
             if c["entry"]["name"] in m.get("workloads", [c["entry"]["name"]])}
    rate = window.steps() * items / window.span_s() / chips
    values = {"setup_s": setup_s, c["cell"]["rate_metric"]: rate,
              "step_p95_ms": 1e3 * drivers.percentile(window.intervals_s(), 95)}
    return {n: {"value": values[n], "unit": m["unit"]}
            for n, m in names.items()}


def run(c: dict, a, devices, keep: dict | None = None) -> dict:
    """The result line's object.  ``keep``, where given, receives what a look
    at the limits needs after the run (the batches the compared steps were
    fed, both sides' readings, the limits): ``tests/benchmark/read_limits.py``."""
    import examples.common as common  # compile cache on, before any compile
    from benchmark import compare, families, program, traffic, weights
    from benchmark.reference import train as ref_train
    from benchmark.window import StepTap, Window, WindowClosed
    from tpucfn.data import prefetch_to_mesh

    config, mix, cell = c["config"], c["mix"], c["cell"]
    chips = len(devices)
    # a fixed place inside the checkout; a rehearsal (the tests run several at
    # once) gets one of its own
    work = ROOT / ".cache" / "bench" / (
        f"rehearse-{os.getpid()}" if a.rehearse else a.workload)
    shutil.rmtree(work, ignore_errors=True)
    run_dir = work / "run"

    clock.mark("imports")
    records = traffic.make_records(mix, config["model"], a.seed)
    clock.mark("records")
    shards = program.stage(mix, records, run_dir / "data")
    clock.mark("shards")
    trainer, ds, mesh, args, items = program.build(
        config, mix, cell, shards, a.seed, run_dir, devices)

    check_steps = cell["check"]["steps"]
    depth = inspect.signature(prefetch_to_mesh).parameters["depth"].default
    trace_dir = work / "profile" if a.trace else None
    window = Window(
        ds, seconds=a.seconds,
        warmup_pulls=cell["loop"]["warmup_steps"] + depth + 1,
        keep_batches=check_steps, trace_dir=trace_dir,
        trace_seconds=min(cell["loop"]["trace_seconds"], a.seconds),
        profiler=cell["loop"].get("profiler", {}),
        background_threads=cell["loop"].get("background_threads", ()))
    spec = families.load(config["family"]).reference.param_spec(config["model"])
    tap = StepTap(trainer, steps=check_steps, spec=spec,
                  key=weights.seed_key(a.seed))

    with contextlib.redirect_stdout(sys.stderr):
        try:
            common.run_train_loop(trainer, window, mesh, args,
                                  items_per_step=items)
        except WindowClosed:
            pass
        finally:
            window.abandon()
    if not window.closed:
        raise SystemExit("the stream ended before the window closed")
    setup_s = window.pulls[window.open_index] - clock.T0
    for i in (0, 1, check_steps + depth + 1):
        clock.mark(f"pull_{i}", window.pulls[i])
    clock.mark("window_open", window.pulls[window.open_index])
    clock.mark("window_close", window.pulls[window.close_index])
    clock.mark("loop_left")

    peak_bytes = max(drivers.device_peak_bytes(d) for d in devices)
    prog = tap.readings()
    kernel_calls = None
    if not a.rehearse:
        kernel_calls = program.kernel_calls(trainer, mesh, window.kept[0])
    del trainer, tap, ds
    gc.collect()

    # ---- the comparison, once the program's state is freed ----------------
    clock.mark("program_freed")
    ref = ref_train.follow(config, a.seed, window.kept)
    clock.mark("reference")
    values = compare.numbers(prog, ref)
    values["input_mismatch"] = traffic.input_mismatches(
        mix, config["model"], records, window.kept, a.seed)
    if kernel_calls is not None:
        values["kernel_path_mismatch"] = int(
            (kernel_calls > 0) != cell["check"]["flash_kernel"])
    limits = {k: v for k, v in cell["check"]["limits"].items() if k in values}
    correct, table = compare.verdict(values, limits)
    # read and shown, not held to a limit: a number the cell's file gives none
    # (PERF.md says why), and the count behind kernel_path_mismatch
    for k, v in values.items():
        table.setdefault(k, {"value": v, "limit": None})
    if kernel_calls is not None:
        table["kernel_calls"] = {"value": kernel_calls, "limit": None}
    if keep is not None:
        keep.update(batches=window.kept, program=prog, reference=ref,
                    limits=limits)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": window.steps(),
              "failed": 0}
    if a.trace:
        from benchmark import readers

        metrics, breakdown, busy_s, window_s = readers.per_layer(
            c, window, run_dir, trace_dir, devices, a.rehearse)
        result["metrics"] = metrics
        if not a.rehearse:
            device.update(busy_s=busy_s, window_s=window_s)
            result["breakdown"] = breakdown
    else:
        result["metrics"] = end_to_end(c, window, setup_s, items, chips)
        if a.rehearse:  # no CPU number under a device metric's name
            result["metrics"] = {}
            result["rehearsal"] = {"steps": window.steps(),
                                   "span_s": window.span_s()}
    clock.mark("metrics")
    result["device"] = device
    result["timeline"] = list(clock.MARKS)
    result["step_intervals_s"] = [round(x, 4) for x in window.intervals_s()]
    result["compared"] = table
    shutil.rmtree(work, ignore_errors=True)
    return result
