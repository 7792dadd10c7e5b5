"""The fit of the profiler's clock to the host's and the division of the
device's idle time among the loop's phases: on hand-made events, and on a few
steps recorded from a traced chip run of ``rn50-cached`` (PR 25)."""

import gzip
import json
from pathlib import Path

import pytest

from benchmark import readers
from benchmark.readers import device_idle_share, idle_in_span, trace

SAMPLE = Path(__file__).parent / "data" / "idle_in_span_sample.json.gz"
PHASES = {"data_wait": {"span": "data_wait"}, "step_wait": {"span": "step_wait"},
          "step_dispatch": {"span": "step_dispatch"},
          "loop": {"outside": ["data_wait", "step_dispatch", "step_wait", "ckpt"]}}
THETA = 100.0   # host clock minus trace clock, as the events below are made

# trace clock: four runs of the step's module at irregular distances, the
# device busy for the whole of each
RUNS = [(1.0, 2.0), (3.0, 4.0), (5.5, 6.5), (7.0, 8.0)]
# host clock: (launch, mark, woke) of seven steps; steps 2..5 are the runs.
# launch precedes the run's start by 0.1, 0.3, 0.2, 0.05 and the host wakes
# 0, 0.1, 0.05, 0.02 after its end: the window is [99.95, 100.0]
STEPS = [(95.0, 95.1, 96.2), (97.0, 97.1, 98.9),
         (100.9, 100.95, 102.0), (102.7, 102.8, 104.1),
         (105.3, 105.4, 106.55), (106.95, 107.0, 108.02),
         (108.5, 108.6, 109.9)]
LOOP_S = 0.1    # logger and the loop's own statements after each step


def device(runs=RUNS, name="/device:TPU:0"):
    return trace.DeviceTrace(
        name, [("fusion.1", a, b - a) for a, b in runs],
        [("jit__step_fn(7)", a, b - a) for a, b in runs])


def program_spans(steps=STEPS, tid="MainThread", split=True):
    rows, ids = [], iter(range(1, 10_000))

    def span(name, step, start, end, parent=None, thread=tid):
        rows.append({"kind": "span", "name": name, "trace_id": step,
                     "span_id": next(ids), "parent_id": parent, "start": start,
                     "dur_s": end - start, "tid": thread})
        return rows[-1]["span_id"]

    for k, (launch, mark, woke) in enumerate(steps):
        if k:
            span("data_wait", k, steps[k - 1][2] + LOOP_S, launch)
        # the loader is at work all the time, on its own thread
        span("input_load", k + 2, launch - 0.5, launch + 0.2,
             thread="tpucfn-prefetch")
        span("input_place", k + 2, launch + 0.2, launch + 0.9,
             thread="tpucfn-prefetch")
        step = span("step", k, launch, woke)
        if split:
            span("step_dispatch", k, launch, mark, parent=step)
            span("step_wait", k, mark, woke, parent=step)
    if not split:
        for r in rows:
            del r["tid"]     # a program from before this PR
    return rows


def ctx(devices, spans, near=THETA, skip=0):
    return readers.Context(
        config={}, mix={}, chips=len(devices or [0]), spans=spans,
        host_interval=(near, near + 9.0), devices=devices,
        step_module="_step_fn", peak=None, skip_steps=skip)


def phases(c):
    return {k: idle_in_span.read(c, **v) for k, v in PHASES.items()}


@pytest.fixture
def wake(monkeypatch):
    """The hand-made steps wake 0.025 s after a run's end at the least."""
    monkeypatch.setattr(idle_in_span, "WAKE_S", 0.025)


def test_the_window_is_two_sided_and_theta_lies_the_wake_up_below_its_top(
        monkeypatch):
    steps, leaves = idle_in_span.loop_thread(program_spans())
    assert steps == [(a, c) for a, _, c in STEPS]
    assert {n for _, _, n in leaves} == {"data_wait", "step_dispatch",
                                         "step_wait"}
    f = idle_in_span.fit(steps, RUNS, near=THETA)
    assert f.shift == 2 and f.shifts == [2]
    assert f.lo == pytest.approx(99.95) and f.hi == pytest.approx(100.0)
    assert f.theta == f.hi - idle_in_span.WAKE_S == pytest.approx(99.998)
    # a window narrower than the wake-up: theta stays inside it
    monkeypatch.setattr(idle_in_span, "WAKE_S", 0.2)
    assert idle_in_span.fit(steps, RUNS, near=THETA).theta == f.lo


@pytest.mark.parametrize("shift", [0, 1, 3])
def test_a_wrong_shift_leaves_no_window(shift):
    """The irregular distances between the runs make one pairing the only one
    that causality allows."""
    steps = [(a, c) for a, _, c in STEPS][shift:shift + len(RUNS)]
    assert idle_in_span.fit(steps, RUNS, near=THETA) is None
    right = [(a, c) for a, _, c in STEPS][2:2 + len(RUNS)]
    assert idle_in_span.fit(right, RUNS, near=THETA).shift == 0


def test_the_four_phases_are_read_and_sum_to_the_idle_share(wake):
    c = ctx([device()], program_spans())
    # the cut is 1.0..7.0; idle 2.0-3.0, 4.0-5.5, 6.5-7.0 on the trace's clock
    assert device_idle_share.read(c) == pytest.approx(50.0)
    got = phases(c)
    assert got["data_wait"] == pytest.approx(100 * 2.0 / 6.0)
    # theta is 99.975, 0.025 below the window's top: the gaps lie that much
    # earlier on the host's clock than the events were made
    assert got["step_wait"] == pytest.approx(100 * 0.475 / 6.0)
    assert got["step_dispatch"] == pytest.approx(100 * 0.225 / 6.0)
    assert got["loop"] == pytest.approx(100 * 0.30 / 6.0)
    assert sum(got.values()) == pytest.approx(device_idle_share.read(c))
    # with the leading step left out, as the benchmark's cut leaves it
    c1 = ctx([device()], program_spans(), skip=1)
    assert sum(phases(c1).values()) == pytest.approx(device_idle_share.read(c1))
    assert phases(c1)["data_wait"] == pytest.approx(100 * 1.4 / 4.0)


def test_the_mean_is_over_the_chips(wake):
    late = [(a + 0.01, b + 0.01) for a, b in RUNS]   # still inside the window
    c = ctx([device(), device(late, "/device:TPU:1")], program_spans())
    assert sum(phases(c).values()) == pytest.approx(device_idle_share.read(c))


def test_periodic_steps_take_the_shift_nearest_the_profilers_start(wake):
    """Equal distances leave a window for every shift; each puts the idle time
    into the same phases, and the one nearest the host's reading at the start
    of the trace is taken."""
    runs = [(1.0 + 2 * j, 2.5 + 2 * j) for j in range(3)]
    steps = [(90.95 + 2 * k, 91.0 + 2 * k, 92.51 + 2 * k) for k in range(8)]
    host = [(a, c) for a, _, c in steps]
    f = idle_in_span.fit(host, runs, near=100.0)
    assert f.shifts == [0, 1, 2, 3, 4, 5]
    assert f.shift == 5 and f.theta == pytest.approx(99.985)
    assert idle_in_span.fit(host, runs, near=94.2).shift == 2
    got = [phases(ctx([device(runs)], program_spans(steps), near=n))
           for n in (100.0, 94.2)]
    assert got[0] == pytest.approx(got[1])
    assert sum(got[0].values()) == pytest.approx(25.0)


def test_no_shift_fits_no_number_and_the_reason_on_stderr(capsys):
    short = [(a, a + 0.05, a + 0.5) for a, _, _ in STEPS]   # wake before the end
    c = ctx([device()], program_spans(short))
    assert phases(c) == dict.fromkeys(PHASES)
    assert "no pairing" in capsys.readouterr().err


def test_a_program_without_the_spans_gives_nothing_and_does_not_raise():
    c = ctx([device()], program_spans(split=False))
    assert phases(c) == dict.fromkeys(PHASES)
    assert phases(ctx(None, program_spans())) == dict.fromkeys(PHASES)
    assert phases(ctx([device(RUNS[:1])], program_spans())) == dict.fromkeys(PHASES)


def test_the_new_metric_files_name_this_reader_and_the_phases():
    metrics = Path(readers.METRICS)
    for sfx in (".rn", ".lm"):
        for phase, args in PHASES.items():
            m = json.loads((metrics / f"idle_in_{phase}{sfx}.json").read_text())
            assert m["reader"] == "idle_in_span" and m["args"] == args
            assert (m["unit"], m["better"], m["source"]) == (
                "%", "lower", "device_trace")


def test_recorded_sample_from_the_chip():
    """The first six steps of a traced run of rn50-cached on the v5e chip
    (PR 25): modules, operations with their names cut, the program's spans
    around them."""
    with gzip.open(SAMPLE, "rt") as f:
        s = json.load(f)
    dev = trace.DeviceTrace(s["device"]["name"],
                            [tuple(e) for e in s["device"]["ops"]],
                            [tuple(e) for e in s["device"]["modules"]])
    c = readers.Context(config={}, mix={}, chips=1, spans=s["spans"],
                        host_interval=tuple(s["host_interval"]), devices=[dev],
                        step_module=s["step_module"], peak=None, skip_steps=0)
    steps, _ = idle_in_span.loop_thread(c.spans)
    f = idle_in_span.fit_device(c, dev, steps)
    whole = s["fit_on_the_whole_trace"]
    # fewer steps bound the window less: it holds the whole trace's; the
    # wake-up is steady, so its top and theta move by a fraction of a ms
    assert f.lo <= whole["lo"] + 1e-9 and f.hi >= whole["hi"] - 1e-9
    assert f.theta == pytest.approx(whole["theta"], abs=3e-4)
    assert 0 <= f.hi - f.lo <= SAMPLE_WINDOW_S
    got = phases(c)
    assert all(v is not None and v >= 0 for v in got.values())
    assert sum(got.values()) == pytest.approx(device_idle_share.read(c),
                                              abs=1e-6)
    assert 20 < device_idle_share.read(c) < 70


# the width of the window the chip gave over the whole trace (2.305 ms), as an
# upper limit
SAMPLE_WINDOW_S = 0.0025
