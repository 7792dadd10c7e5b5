"""Per-layer metrics: one small reader each, found by name.

``benchmark/metrics/<name>.json`` states a metric (layer, unit, ``moves``,
cells) and names its reader, a module here with ``read(ctx, **args)`` that
returns the value or ``None`` where it finds nothing to read.  A new metric is
a new ``.json``, and a new reader module where no reader here fits.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from benchmark import by_name
from benchmark.readers import trace

METRICS = Path(__file__).resolve().parent.parent / "metrics"


@dataclasses.dataclass
class Context:
    config: dict
    mix: dict
    chips: int
    spans: list[dict]                 # the program's spans, host monotonic clock
    host_interval: tuple[float, float] | None   # the trace, on that clock
    devices: list[trace.DeviceTrace] | None     # None off the chip
    step_module: str
    peak: dict | None
    skip_steps: int = 0               # leading steps left out of the cut

    def cut(self):
        """Per device (t0, t1, steps) of whole steps; None if any lacks them."""
        if not self.devices:
            return None
        cuts = [trace.step_interval(d, self.step_module, self.skip_steps)
                for d in self.devices]
        return None if any(c is None for c in cuts) else cuts


def program_spans(run_dir: Path) -> list[dict]:
    rows = []
    for p in sorted((Path(run_dir) / "trace").glob("trace-*.jsonl")):
        rows += [json.loads(ln) for ln in p.read_text().splitlines() if ln]
    return [r for r in rows if r.get("kind") == "span"]


def metric_files(manifest: dict, cell: str) -> list[dict]:
    out = []
    for m in manifest["per_layer"]:
        if cell in m.get("workloads", [cell]):
            out.append(json.loads((METRICS / f"{m['name']}.json").read_text()))
    return out


def per_layer(c: dict, window, run_dir, trace_dir, devices, rehearse: bool):
    """(metrics, breakdown, busy_s, window_s) of one traced run."""
    from benchmark import flops

    cell = c["entry"]["name"]
    ctx = Context(
        config=c["config"], mix=c["mix"], chips=len(devices),
        spans=program_spans(run_dir), host_interval=window.traced,
        devices=None if rehearse else trace.load(trace_dir),
        step_module=c["cell"]["loop"]["step_module"],
        peak=None if rehearse else flops.peaks(devices[0].device_kind),
        # starting the profiler stalls the loop once, for up to a second,
        # inside the step that was running (PERF.md, PR 24)
        skip_steps=1)
    metrics = {}
    for m in metric_files(c["manifest"], cell):
        if rehearse and m["source"] == "device_trace":
            continue  # no CPU number under a device metric's name
        reader = by_name("readers", m["reader"], "reader")
        value = reader.read(ctx, **m.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if rehearse:
        return metrics, None, None, None
    cuts = ctx.cut()
    if cuts is None:
        raise SystemExit("the trace holds fewer than three runs of the step's "
                         f"module ({ctx.step_module!r}) on some device")
    busy = [trace.busy_seconds(trace.clip(d.ops, t0, t1))
            for d, (t0, t1, _) in zip(ctx.devices, cuts)]
    window_s = sum(t1 - t0 for t0, t1, _ in cuts) / len(cuts)
    return metrics, breakdown(ctx, cuts), sum(busy) / len(busy), window_s


def breakdown(ctx: Context, cuts) -> dict:
    """The device operations that took most time, and the longest idle gaps
    with the program span that was open in each.  The program's spans are on
    the host's monotonic clock and the trace on the profiler's: they are laid
    over each other at the first step (the first ``step`` span's start on the
    first run of the step's module), which is right to a dispatch's latency."""
    dev, (t0, t1, _) = ctx.devices[0], cuts[0]
    ops = trace.self_seconds(trace.clip(dev.ops, t0, t1))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(trace.idle_gaps(dev.ops, t0, t1), key=lambda g: g[0] - g[1])[:10]
    steps = sorted((s for s in ctx.spans if s["name"] == "step"
                    and ctx.host_interval
                    and s["start"] >= ctx.host_interval[0]),
                   key=lambda s: s["start"])
    shift = steps[0]["start"] - t0 if steps else None

    def open_span(at: float) -> str:
        if shift is None:
            return "no program span on this clock"
        host = at + shift
        for s in ctx.spans:
            if s["start"] <= host <= s["start"] + s["dur_s"]:
                return f"in {s['name']} span"
        return "between spans (loop, logger)"

    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[open_span((a + b) / 2), b - a] for a, b in gaps
                          if b > a]}
