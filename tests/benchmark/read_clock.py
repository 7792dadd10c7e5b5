"""A look at the two clocks, taken on the chip at a cell's own size:
``python tests/benchmark/read_clock.py --workload <name> --seed <n> --seconds
<s> --out <dir>``.

One traced run of the cell through the benchmark's own driver.  Kept under
``--out`` before anything is worked out from them: the program's spans
(``spans.jsonl``, every step of the run from the first), the reduced trace
(``devices.json.gz``) and the traced interval (``host.json``).  Then printed,
one JSON line (and kept as ``summary.json``):

- the loop's step intervals (``step`` span to ``step`` span) inside the trace
  and after it: what the trace costs while it is on;
- per device the fit of ``readers/idle_in_span.py``: window, theta, shift, the
  shifts that leave a window;
- theta by the profile's own start: the ``Task Environment`` plane holds
  ``profile_start_time``, the wall-clock time of the trace's zero, and every
  span holds both clocks (``ts`` and ``start``); and how far the fit lies
  from it (the reduction in ``readers/trace.py`` does not keep that plane,
  so the reader has the fit alone);
- the loader thread's two spans and how many batches were ``queued``;
- the run's per-layer metrics, the four ``idle_in_*`` beside
  ``device_idle_share`` among them.

``--sample <file>`` also writes the few steps the tests keep: modules,
operations with names cut to 40 characters, the spans of those steps, the
traced interval.  The benchmark's own runs never come here.
"""

import argparse
import collections
import gzip
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import drivers, readers, run  # noqa: E402
from benchmark.readers import idle_in_span, trace  # noqa: E402

SAMPLE_STEPS = 6
SAMPLE_NAME_CHARS = 40


def theta_by_profile_start(trace_dir, spans) -> float | None:
    """The host's monotonic reading at the trace's zero."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(
        str(sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]))
    for plane in data.planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats).get("profile_start_time")
            if start_ns and spans:
                wall_minus_mono = statistics.median(
                    s["ts"] - s["start"] for s in spans)
                return start_ns / 1e9 - wall_minus_mono
    return None


def spread(values) -> dict | None:
    if len(values) < 2:
        return None
    s = sorted(values)
    return {"n": len(s), "median": statistics.median(s),
            "p95": drivers.percentile(s, 95), "max": s[-1]}


def step_intervals(spans, host_interval) -> dict:
    """Start-to-start intervals of the loop's steps: inside the trace (the
    interval in which the profiler started left out) and after it (the one in
    which it stopped left out)."""
    starts = sorted(s["start"] for s in spans if s["name"] == "step")
    h0, h1 = host_interval
    pairs = list(zip(starts, starts[1:]))
    inside = [b - a for a, b in pairs if a >= h0 and b <= h1]
    after = [b - a for a, b in pairs if a > h1][1:]
    return {"inside": spread(inside), "after": spread(after)}


def loader(spans) -> dict:
    """The loader thread's spans over the whole run: seconds a batch in each,
    and how many batches were queued when a batch was ready."""
    out = {}
    for name in ("input_load", "input_place"):
        out[name] = spread([s["dur_s"] for s in spans if s["name"] == name])
    out["queued"] = dict(sorted(collections.Counter(
        str(s["attrs"]["queued"]) for s in spans
        if s["name"] == "input_place").items()))
    return out


def sample(ctx, devices, path) -> None:
    """The first few steps of the trace, small enough to keep.  The step in
    which the profiler started is among them: in ``rn50-cached`` it alone
    bounds the window from below to better than the wait for a transfer."""
    dev = devices[0]
    keep = [m for m in dev.modules if ctx.step_module in m[0]][:SAMPLE_STEPS]
    t0, t1 = keep[0][1], keep[-1][1] + keep[-1][2]
    steps, _ = idle_in_span.loop_thread(ctx.spans)
    f = idle_in_span.fit_device(ctx, dev, steps)
    a, b = t0 + f.theta - 1.0, t1 + f.theta + 1.0
    out = {
        "device": {"name": dev.name, "modules": keep,
                   "ops": [(n[:SAMPLE_NAME_CHARS], s, d) for n, s, d in dev.ops
                           if s + d > t0 and s < t1]},
        "spans": [{k: s.get(k) for k in ("name", "trace_id", "span_id",
                                         "parent_id", "start", "dur_s", "tid")}
                  for s in ctx.spans if a <= s["start"] <= b],
        "host_interval": list(ctx.host_interval),
        "step_module": ctx.step_module,
        "fit_on_the_whole_trace": {"theta": f.theta, "lo": f.lo, "hi": f.hi}}
    with gzip.open(path, "wt") as fh:
        json.dump(out, fh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", required=True)
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
        seen["theta"] = theta_by_profile_start(trace_dir, seen["spans"])
        (out / "host.json").write_text(json.dumps(
            {"host_interval": window.traced,
             "theta_by_profile_start": seen["theta"]}))
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
               "correct": result["correct"],
               "device": result["device"], "metrics": result["metrics"],
               "step_intervals_s": step_intervals(seen["spans"],
                                                  seen["host_interval"]),
               "loader": loader(seen["spans"]),
               "theta_by_profile_start": seen["theta"]}
    if not a.rehearse:
        ctx = readers.Context(
            config=c["config"], mix=c["mix"], chips=len(devices),
            spans=seen["spans"], host_interval=seen["host_interval"],
            devices=seen["devices"],
            step_module=c["cell"]["loop"]["step_module"], peak=None,
            skip_steps=1)
        steps, _ = idle_in_span.loop_thread(ctx.spans)
        fits = [idle_in_span.fit_device(ctx, d, steps) for d in ctx.devices]
        summary["fit"] = [f and {"theta": f.theta, "lo": f.lo, "hi": f.hi,
                                 "width_s": f.hi - f.lo, "shift": f.shift,
                                 "shifts": f.shifts} for f in fits]
        if fits[0] and seen["theta"] is not None:
            summary["fit_minus_profile_start_s"] = fits[0].theta - seen["theta"]
        if a.sample and fits[0]:
            sample(ctx, ctx.devices, a.sample)
    (out / "summary.json").write_text(json.dumps(summary))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
