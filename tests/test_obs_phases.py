"""The host's phases from inside the program (ISSUE 25): the ``step`` span
divided at one clock reading, the loader thread's two spans, ``tid`` on every
line, the one place a profiler capture starts, and the existing readers of a
trace left as they were by the new names."""

import json
import threading
import time

import numpy as np
import pytest

from tpucfn.obs import MetricRegistry, Tracer, read_trace_file
from tpucfn.train.trainer import TrainerObs

NEW_NAMES = ("step_dispatch", "step_wait", "input_load", "input_place")


def spans(path):
    return [e for e in read_trace_file(path) if e["kind"] == "span"]


# ---- the step, divided ----------------------------------------------------

def test_a_marked_step_has_one_dispatch_and_one_wait_summing_to_it(tmp_path):
    tracer = Tracer(tmp_path / "t.jsonl", host_id=0, role="trainer")
    obs = TrainerObs(MetricRegistry(), tracer)
    for n in (1, 2, 3):
        with obs.step(n) as mark:
            time.sleep(0.002)
            mark.dispatched()
            time.sleep(0.001)
    tracer.close()
    rows = spans(tmp_path / "t.jsonl")
    for n in (1, 2, 3):
        mine = [r for r in rows if r["trace_id"] == n]
        assert sorted(r["name"] for r in mine) == [
            "step", "step_dispatch", "step_wait"]
        step, dispatch, wait = (next(r for r in mine if r["name"] == k)
                                for k in ("step", "step_dispatch", "step_wait"))
        assert dispatch["parent_id"] == wait["parent_id"] == step["span_id"]
        assert step["parent_id"] is None
        assert dispatch["start"] == step["start"]
        assert wait["start"] == dispatch["start"] + dispatch["dur_s"]
        assert dispatch["dur_s"] + wait["dur_s"] == pytest.approx(
            step["dur_s"], abs=1e-9)
        assert dispatch["dur_s"] >= 0.002 and wait["dur_s"] >= 0.001


def test_an_unmarked_step_writes_the_step_alone_and_counts_as_before(tmp_path):
    tracer = Tracer(tmp_path / "t.jsonl")
    registry = MetricRegistry()
    obs = TrainerObs(registry, tracer)
    with obs.step(5):
        pass
    tracer.close()
    assert [r["name"] for r in spans(tmp_path / "t.jsonl")] == ["step"]
    v = registry.varz()["metrics"]
    assert v["train_steps_total"] == 1.0 and v["train_last_step"] == 5.0
    assert v["train_step_seconds"]["count"] == 1


def test_the_mark_is_read_on_the_clock_the_gauges_use(tmp_path):
    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    tracer = Tracer(tmp_path / "t.jsonl")
    obs = TrainerObs(MetricRegistry(), tracer, clock=clk)
    for n in (1, 2):
        with obs.step(n) as mark:
            clk.t += 0.25
            mark.dispatched()
            clk.t += 0.75
        assert mark.at == clk.t - 0.75
    tracer.close()
    assert obs.registry.varz()["metrics"]["train_step_time_s"] == 1.0
    assert [(r["name"], r["start"], r["dur_s"]) for r in spans(
        tmp_path / "t.jsonl") if r["trace_id"] == 2] == [
        ("step", 1.0, 1.0), ("step_dispatch", 1.0, 0.25),
        ("step_wait", 1.25, 0.75)]


# ---- every line says which thread wrote it; one builder of the row ---------

def test_span_and_record_write_the_same_row_with_the_threads_name(tmp_path):
    tracer = Tracer(tmp_path / "t.jsonl", host_id=2, role="trainer")
    with tracer.span("by_span", trace_id=1, a=1):
        pass
    tracer.record("by_record", start=time.monotonic(), dur_s=0.0, trace_id=1)
    t = threading.Thread(target=lambda: tracer.event("from_thread"),
                         name="some-loader")
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    tracer.close()
    a, b, c = read_trace_file(tmp_path / "t.jsonl")
    assert set(a) == set(b) == set(c)
    assert a["tid"] == b["tid"] == threading.current_thread().name
    assert c["tid"] == "some-loader"
    assert a["attrs"] == {"a": 1} and a["parent_id"] is None
    assert not hasattr(Tracer, "_write_span")


# ---- the loader's thread ---------------------------------------------------

def host_batches(n):
    return [{"x": np.full((8, 4), i, np.float32),
             "y": np.full((8,), i, np.int32)} for i in range(n)]


def test_prefetch_with_a_tracer_writes_load_and_place_per_batch(
        tmp_path, mesh_dp8):
    from tpucfn.data.pipeline import prefetch_to_mesh

    tracer = Tracer(tmp_path / "t.jsonl")
    out = list(prefetch_to_mesh(iter(host_batches(5)), mesh_dp8,
                                tracer=tracer, first_step=41))
    tracer.close()
    assert [int(b["x"][0, 0]) for b in out] == [0, 1, 2, 3, 4]
    rows = spans(tmp_path / "t.jsonl")
    assert {r["tid"] for r in rows} == {"tpucfn-prefetch"}
    loads = [r for r in rows if r["name"] == "input_load"]
    places = [r for r in rows if r["name"] == "input_place"]
    assert [r["trace_id"] for r in places] == [41, 42, 43, 44, 45]
    # one more pull found the stream's end
    assert [r["trace_id"] for r in loads] == [41, 42, 43, 44, 45, 46]
    assert loads[-1]["attrs"] == {"end_of_stream": True}
    nbytes = 8 * 4 * 4 + 8 * 4
    assert all(r["attrs"]["bytes"] == nbytes for r in loads[:-1] + places)
    assert all(0 <= r["attrs"]["queued"] <= 2 for r in places)
    assert all(r["parent_id"] is None for r in rows)


def test_prefetch_without_a_tracer_yields_as_before(mesh_dp8):
    from tpucfn.data.pipeline import prefetch_to_mesh
    from tpucfn.parallel.sharding import shard_batch

    got = list(prefetch_to_mesh(iter(host_batches(4)), mesh_dp8))
    want = [shard_batch(mesh_dp8, b, ()) for b in host_batches(4)]
    assert len(got) == 4
    for g, w in zip(got, want):
        assert g["x"].sharding == w["x"].sharding
        np.testing.assert_array_equal(np.asarray(g["x"]), np.asarray(w["x"]))
        np.testing.assert_array_equal(np.asarray(g["y"]), np.asarray(w["y"]))


# ---- the readers the tree had pass the new names by ------------------------

def fleet(with_new: bool):
    """Two trainer hosts, three steps each: the spans the loop wrote before
    this issue and, where asked, the ones it writes now."""
    rows, sid = [], iter(range(1, 10_000))

    def span(host, name, step, start, dur, tid="MainThread", parent=None):
        row = {"kind": "span", "name": name, "trace_id": step,
               "span_id": next(sid), "parent_id": parent, "start": start,
               "dur_s": dur, "ts": 1000.0 + start, "mono": start + dur,
               "host": host, "role": "trainer", "tid": tid, "attrs": {}}
        rows.append(row)
        return row["span_id"]

    for host in (0, 1):
        for k in (1, 2, 3):
            t = 10.0 * k + host
            span(host, "data_wait", k, t, 0.2)
            step = span(host, "step", k, t + 0.2, 0.7)
            span(host, "ckpt", k, t + 0.9, 0.1)
            if with_new:
                span(host, "step_dispatch", k, t + 0.2, 0.1, parent=step)
                span(host, "step_wait", k, t + 0.3, 0.6, parent=step)
                span(host, "input_load", k + 2, t, 0.3, "tpucfn-prefetch")
                span(host, "input_place", k + 2, t + 0.3, 0.4,
                     "tpucfn-prefetch")
    return rows


def write_trace_dir(d, rows):
    d.mkdir(parents=True)
    for host in (0, 1):
        (d / f"trace-trainer-host{host:03d}.jsonl").write_text("".join(
            json.dumps(r) + "\n" for r in rows if r["host"] == host))


def test_aggregate_and_timeline_read_the_same_with_and_without_the_new_names(
        tmp_path):
    from tpucfn.obs.aggregate import (host_straggler_report,
                                      merge_step_timeline, step_spans_by_host)
    from tpucfn.obs.timeline import (critical_path, merge_timeline,
                                     render_critpath)

    out = {}
    for with_new in (False, True):
        rows = fleet(with_new)
        assert with_new == any(r["name"] in NEW_NAMES for r in rows)
        d = tmp_path / str(with_new) / "trace"
        write_trace_dir(d, rows)
        by_host = step_spans_by_host(rows)
        merged = merge_timeline(d)
        cp = critical_path(merged)
        out[with_new] = {
            "by_host": by_host,
            "timeline": merge_step_timeline(by_host),
            "stragglers": host_straggler_report(by_host),
            "critpath": cp, "critpath_text": render_critpath(cp),
            "link_stats": merged["link_stats"], "skew": merged["skew"]}
    assert out[True] == out[False]
    assert len(out[True]["critpath"]["steps"]) == 6


# ---- a capture through the one place a trace starts ------------------------

def test_both_entry_points_start_their_trace_in_one_place_device_only(
        tmp_path, monkeypatch):
    import jax

    from tpucfn.obs import ProfileCapture, profile_steps

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: calls.append((d, profiler_options)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    with profile_steps(tmp_path / "a"):
        pass
    out = ProfileCapture(tmp_path / "b", sleep=lambda s: None)(0.5)
    assert [c for c in calls if c == "stop"] == ["stop", "stop"]
    (a, opts_a), (b, opts_b) = [c for c in calls if c != "stop"]
    assert a == str(tmp_path / "a") and b == out["artifact"]
    for opts in (opts_a, opts_b):
        # the host's tracer stalls the loop it looks at (PERF.md, PR 24, 25)
        assert opts.host_tracer_level == 0 and opts.python_tracer_level == 0


def test_a_capture_writes_a_trace_and_the_loop_writes_no_annotation(tmp_path):
    """An annotation that no capture can hold is not written: the phases are
    the trace file's, on the host's clock."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from tpucfn.obs.profiler import trace_to

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = Tracer(tmp_path / "t.jsonl")
    obs = TrainerObs(MetricRegistry(), tracer)
    with trace_to(tmp_path / "capture"):
        with obs.step(7) as mark:
            y = f(x)
            mark.dispatched()
            y.block_until_ready()
    tracer.close()
    [path] = (tmp_path / "capture").rglob("*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events}
    assert not names & {"step", "step_dispatch", "step_wait"}
    assert [r["name"] for r in spans(tmp_path / "t.jsonl")] == [
        "step", "step_dispatch", "step_wait"]
