"""The readers that join the program's ``step_program`` span with a device
trace (``scope_time_share``) and read a span's own duration
(``span_seconds``): over a synthetic context, and over a few steps of
``mistral7b-s1024`` recorded on the chip (``data/scope_sample.json.gz``,
written by ``read_scopes.py --sample``)."""

import gzip
import json
import sys
from pathlib import Path

import pytest

from benchmark import readers
from benchmark.readers import scope_time_share, span_seconds, trace

HERE = Path(__file__).resolve().parent
SAMPLE = HERE / "data" / "scope_sample.json.gz"
METRICS = HERE.parent.parent / "benchmark" / "metrics"
NEW = sorted(p.stem for p in METRICS.glob("*.json")
             if json.loads(p.read_text())["reader"] in ("scope_time_share",
                                                        "span_seconds"))
PASS_METRICS = ["pass_forward_time_share", "pass_remat_time_share",
                "pass_backward_time_share", "pass_optimizer_time_share",
                "scope_unmapped_time_share"]

CLASSES = [["forward", "M/layers/mlp", "convolution", True],       # 0
           ["remat", "M/layers/mlp", "convolution", True],         # 1
           ["backward", "M/layers/mlp/up", "dynamic-update-slice", True],
           ["backward", "M", "dynamic-update-slice", False],       # 3
           ["optimizer", "optimizer", "multiply", False],          # 4
           ["none", "", "copy-done", False],                       # 5
           ["forward", "lm_head", "convolution", True],            # 6
           ["backward", "M", "while", False]]                      # 7
OPS = {"fusion.1": 0, "fusion.2": 1, "fusion.3": 2, "fusion.4": 3,
       "fusion.5": 4, "copy-done.6": 5, "fusion.7": 6, "while.8": 7}


def event(name: str, start: float, dur: float):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", start, dur)


def one_step(t: float):
    """A step of 1.0 s, 0.9 busy: a loop's shell of 0.5 around two of its
    body's operations, and an operation the map does not hold."""
    return [event("fusion.1", t, 0.1),
            event("fusion.7", t + 0.1, 0.05),
            event("while.8", t + 0.15, 0.5),
            event("fusion.2", t + 0.15, 0.2),       # inside the loop
            event("fusion.3", t + 0.35, 0.25),      # inside the loop
            event("fusion.4", t + 0.65, 0.05),
            event("fusion.5", t + 0.7, 0.1),
            event("copy-done.6", t + 0.8, 0.02),
            event("fusion.999", t + 0.82, 0.08)]


def program_span(start: float, dur: float, label="train_step", **attrs):
    return {"kind": "span", "name": "step_program", "span_id": 7,
            "parent_id": 3, "trace_id": 1, "start": start, "dur_s": dur,
            "attrs": {"label": label, "classes": CLASSES, "ops": OPS,
                      "temp_bytes": 1234, **attrs}}


def context(spans, steps=4, host_interval=(100.0, 106.0)):
    ops = [e for k in range(steps) for e in one_step(10.0 + k)]
    modules = [("jit__step_fn(1)", 10.0 + k, 0.9) for k in range(steps)]
    return readers.Context(
        config={}, mix={}, chips=1, spans=spans, host_interval=host_interval,
        devices=[trace.DeviceTrace("/device:TPU:0", ops, modules)],
        step_module="_step_fn", peak=None, skip_steps=1)


def args_of(metric: str) -> dict:
    return json.loads((METRICS / f"{metric}.json").read_text())["args"]


def test_the_passes_and_the_unmapped_share_sum_to_100():
    ctx = context([program_span(50.0, 20.0)])
    got = {m: scope_time_share.read(ctx, **args_of(m)) for m in PASS_METRICS}
    assert sum(got.values()) == pytest.approx(100.0, abs=1e-9)
    # of 0.9 busy seconds a step: the loop's shell keeps 0.05 of its 0.5
    assert got["pass_forward_time_share"] == pytest.approx(100 * 0.15 / 0.9)
    assert got["pass_remat_time_share"] == pytest.approx(100 * 0.2 / 0.9)
    assert got["pass_backward_time_share"] == pytest.approx(100 * 0.35 / 0.9)
    assert got["pass_optimizer_time_share"] == pytest.approx(100 * 0.1 / 0.9)
    assert got["scope_unmapped_time_share"] == pytest.approx(100 * 0.1 / 0.9)


def test_unmapped_alone_selects_the_events_the_map_does_not_hold():
    ctx = context([program_span(50.0, 20.0)])
    assert scope_time_share.read(ctx, unmapped=True) == pytest.approx(
        100 * 0.08 / 0.9)
    assert scope_time_share.read(ctx) == pytest.approx(100 * 0.82 / 0.9)


def test_stack_writes_with_and_without_a_product():
    ctx = context([program_span(50.0, 20.0)])
    writes = scope_time_share.read(ctx, **args_of("grad_write_time_share"))
    bare = scope_time_share.read(ctx, **args_of("grad_write_bare_time_share"))
    assert writes == pytest.approx(100 * 0.3 / 0.9)
    assert bare == pytest.approx(100 * 0.05 / 0.9)
    assert scope_time_share.read(ctx, **args_of("head_time_share")) \
        == pytest.approx(100 * 0.05 / 0.9)
    assert scope_time_share.read(ctx, scope=r"M/layers/mlp(/|$)") \
        == pytest.approx(100 * 0.55 / 0.9)


def test_no_span_no_cut_and_no_interval_read_none():
    assert scope_time_share.read(context([])) is None
    assert scope_time_share.read(
        context([program_span(50.0, 20.0, label="train_eval")])) is None
    assert scope_time_share.read(
        context([program_span(50.0, 20.0)], steps=2)) is None   # no cut
    assert scope_time_share.read(
        context([program_span(50.0, 20.0)], host_interval=None)) is None


def test_a_span_that_closed_after_the_trace_began_is_not_taken():
    late = program_span(90.0, 20.0)     # still compiling at 100.0
    assert scope_time_share.read(context([late])) is None
    # the newest that had closed is: its map, not the later one's
    early = program_span(40.0, 5.0)
    early["attrs"] = {**early["attrs"], "ops": {"fusion.1": 0}}
    newer = program_span(60.0, 5.0)
    ctx = context([early, newer, late])
    assert scope_time_share.program(ctx) is newer
    assert scope_time_share.read(context([early, late]),
                                 passes=["forward"]) \
        == pytest.approx(100 * 0.1 / 0.9)


def test_span_seconds_reads_the_newest_span_of_a_name_and_label():
    spans = [program_span(50.0, 20.0), program_span(80.0, 3.0),
             program_span(90.0, 7.0, label="train_eval"),
             {"name": "program_scopes", "start": 69.0, "dur_s": 0.25,
              "attrs": {"label": "train_step"}}]
    ctx = context(spans)
    assert span_seconds.read(ctx, **args_of("step_compile_s")) == 3.0
    assert span_seconds.read(ctx, span="step_program") == 7.0
    assert span_seconds.read(ctx, span="program_scopes") == 0.25
    assert span_seconds.read(ctx, span="step_program", label="train_step",
                             attr="temp_bytes") == 1234.0
    assert span_seconds.read(ctx, span="step_program", attr="absent") is None
    assert span_seconds.read(ctx, span="step_program", label="other") is None
    assert span_seconds.read(context([]), span="step_program") is None


@pytest.mark.parametrize("metric", NEW)
def test_each_new_metric_reads_a_number_through_its_file(metric):
    """The thirteen metric files of ISSUE 35, each through the reader and
    arguments its file names, over the synthetic context."""
    assert len(NEW) == 13
    m = json.loads((METRICS / f"{metric}.json").read_text())
    reader = {"scope_time_share": scope_time_share,
              "span_seconds": span_seconds}[m["reader"]]
    value = reader.read(context([program_span(50.0, 20.0)]), **m["args"])
    assert value is not None and value >= 0.0
    assert m["layer"] == "train step (trainer, models, sharding)"
    assert (m["source"], m["unit"]) == (
        ("program_span", "s") if metric == "step_compile_s"
        else ("device_trace", "%"))
    want = "setup_s" if metric == "step_compile_s" else (
        "images_per_s_per_chip" if metric.endswith(".rn")
        else "tokens_per_s_per_chip")
    assert m["moves"] == want


def test_a_rehearsal_reports_the_span_metric_alone():
    """No CPU number under a device metric's name: off the chip the harness
    skips every ``device_trace`` metric, and ``step_compile_s`` is read."""
    manifest = json.loads((HERE.parent.parent / "BENCHMARK.json").read_text())
    mine = [m for m in readers.metric_files(manifest, "mistral7b-s1024")
            if m["name"] in NEW]
    assert len(mine) == 9
    assert [m["name"] for m in mine if m["source"] != "device_trace"] == [
        "step_compile_s"]


# -- the recorded sample ----------------------------------------------------------

@pytest.fixture(scope="module")
def sample():
    with gzip.open(SAMPLE, "rt") as f:
        kept = json.load(f)
    devices = [trace.DeviceTrace(d["name"], [tuple(e) for e in d["ops"]],
                                 [tuple(e) for e in d["modules"]])
               for d in kept["devices"]]
    return readers.Context(
        config={}, mix={}, chips=1, spans=[kept["span"]],
        host_interval=tuple(kept["host_interval"]), devices=devices,
        step_module=kept["step_module"], peak=None, skip_steps=0)


def test_the_sample_passes_sum_to_100_and_little_is_unmapped(sample):
    got = {m: scope_time_share.read(sample, **args_of(m))
           for m in PASS_METRICS}
    assert sum(got.values()) == pytest.approx(100.0, abs=1e-6)
    assert got["scope_unmapped_time_share"] < 5.0
    assert scope_time_share.read(sample, unmapped=True) < 1.0
    # the cell runs full remat, and an optimizer
    assert got["pass_remat_time_share"] > 5.0
    assert got["pass_optimizer_time_share"] > 0.0
    assert got["pass_backward_time_share"] > got["pass_forward_time_share"]


def test_the_sample_answers_s3(sample):
    """The stack writes of the backward pass hold a product: what is bare
    of one is a small part of them."""
    writes = scope_time_share.read(sample, **args_of("grad_write_time_share"))
    bare = scope_time_share.read(sample,
                                 **args_of("grad_write_bare_time_share"))
    head = scope_time_share.read(sample, **args_of("head_time_share"))
    assert writes > 5.0 and 0.0 <= bare < writes / 4
    assert 2.0 < head < 30.0


def test_the_sample_span_is_one_line_under_its_size(sample):
    span = sample.spans[0]
    assert len(json.dumps(span)) < 512 * 1024
    a = span["attrs"]
    assert a["label"] == "train_step" and a["module"] == "jit__step_fn"
    assert a["instructions"] == len(a["ops"])
    assert a["temp_bytes"] > 2 ** 30     # the run's own planned memory


def test_read_scopes_table_over_the_sample(sample):
    sys.path.insert(0, str(HERE))
    import read_scopes

    t = read_scopes.table(sample, levels=3)
    assert sum(p["s"] for p in t["passes"].values()) == pytest.approx(
        t["busy_s"])
    assert sum(r[2] for r in t["table"]) == pytest.approx(t["busy_s"])
    assert sum(c[4] for c in t["classes"]) + t["classes_tail"]["s"] \
        + t["passes"].get("unmapped", {"s": 0.0})["s"] == pytest.approx(
            t["busy_s"])
    assert len(t["classes"]) == 20
    assert read_scopes.program(sample.spans)[0]["line_bytes"] < 512 * 1024
