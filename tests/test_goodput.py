"""Goodput accounting (ISSUE 5 tentpole): the per-host ledger
decomposes wall clock into buckets that SUM to wall time, re-run steps
land in lost_work, inter-window gaps in restart_downtime — and the
trainer's live efficiency gauges (train_step_time_s /
train_goodput_ratio) are pinned with a fake clock, no TPU involved."""

import json
import urllib.request

import pytest

from tpucfn.obs import MetricRegistry
from tpucfn.obs.goodput import (
    GoodputLedger,
    goodput_report,
    host_goodput,
    host_id_from_path,
    merge_goodput,
    read_goodput_dir,
    read_jsonl_counting,
    render_goodput,
)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _phase(led, clk, bucket, dur, step=None):
    """Real-writer convention: the phase runs, THEN the record is
    stamped — so a record's t is the phase's end."""
    clk.advance(dur)
    led.account(bucket, dur, step=step)


# ---- write side ----------------------------------------------------------

def test_ledger_writes_window_phase_close(tmp_path):
    clk = FakeClock()
    with GoodputLedger(tmp_path, 3, clock=clk, role="trainer") as led:
        assert led.enabled
        _phase(led, clk, "step", 0.5, step=1)
    lines = [json.loads(s) for s in
             (tmp_path / "goodput-host003.jsonl").read_text().splitlines()]
    assert [r["kind"] for r in lines] == ["window", "phase", "close"]
    assert lines[0]["role"] == "trainer" and lines[0]["host"] == 3
    assert lines[1] == {"kind": "phase", "bucket": "step", "dur_s": 0.5,
                        "host": 3, "step": 1, "t": 1000.5}


def test_noop_ledger_never_writes(tmp_path):
    led = GoodputLedger(None)
    assert not led.enabled
    led.account("step", 1.0, step=1)
    led.close()


# ---- decomposition -------------------------------------------------------

@pytest.fixture()
def interrupted_ledger(tmp_path):
    """One host: steps 1-5 (first is compile), ckpt, SIGKILL (no close),
    3 s gap, relaunch re-running steps 4-5 then finishing 6-8."""
    clk = FakeClock()
    led = GoodputLedger(tmp_path, 0, clock=clk)
    _phase(led, clk, "compile", 1.0, step=1)
    for s in range(2, 6):
        _phase(led, clk, "data_wait", 0.1, step=s)
        _phase(led, clk, "step", 0.4, step=s)
    _phase(led, clk, "ckpt", 0.3, step=5)
    led._f.close()  # SIGKILL: no close record
    led._f = None
    clk.advance(3.0)
    led2 = GoodputLedger(tmp_path, 0, clock=clk)
    _phase(led2, clk, "compile", 0.2, step=4)
    for s in range(5, 9):
        _phase(led2, clk, "step", 0.4, step=s)
    led2.close()
    return tmp_path


def test_buckets_sum_to_wall_and_rewind_is_lost_work(interrupted_ledger):
    by_host, skipped = read_goodput_dir(interrupted_ledger)
    assert skipped == 0
    rep = host_goodput(by_host[0])
    b = rep["buckets"]
    # THE invariant: every second of the host's span is in some bucket.
    assert rep["accounted_s"] == pytest.approx(rep["wall_s"])
    assert rep["unaccounted_s"] == pytest.approx(0.0)
    assert rep["windows"] == 2
    assert b["restart_downtime"] == pytest.approx(3.0)
    # step 5 was executed before the kill and re-run after the rewind;
    # the re-run (and only the re-run) is lost work.
    assert rep["lost_steps"] == 1
    assert b["lost_work"] == pytest.approx(0.4)
    assert rep["productive_steps"] == 7  # 2,3,4,5 then 6,7,8
    assert b["productive_step"] == pytest.approx(7 * 0.4)
    assert b["compile"] == pytest.approx(1.2)
    assert b["data_wait"] == pytest.approx(0.4)
    assert b["ckpt"] == pytest.approx(0.3)
    assert 0 < rep["goodput_ratio"] < 1


def test_merge_averages_hosts_and_keeps_invariant(interrupted_ledger):
    # add a second, uninterrupted host with a different span
    clk = FakeClock(2000.0)
    led = GoodputLedger(interrupted_ledger, 1, clock=clk)
    for s in range(1, 4):
        _phase(led, clk, "step", 0.5, step=s)
    led.close()
    by_host, skipped = read_goodput_dir(interrupted_ledger)
    rep = merge_goodput(by_host, skipped_lines=skipped)
    assert rep["num_hosts"] == 2
    assert rep["accounted_s"] == pytest.approx(rep["wall_s"])
    assert rep["wall_s"] == pytest.approx(
        (rep["hosts"]["0"]["wall_s"] + rep["hosts"]["1"]["wall_s"]) / 2)
    assert rep["lost_work_s"] > 0 and rep["restart_downtime_s"] > 0
    assert rep["lost_steps"] == 1
    text = render_goodput(rep)
    assert "restart_downtime" in text and "lost_work" in text


def test_incidents_merge_from_ft_events(interrupted_ledger, tmp_path):
    events = [
        {"ts": 1.0, "kind": "detect", "incident": 1,
         "failures": [{"host": 0, "kind": "crash", "rc": -9}]},
        {"ts": 1.5, "kind": "recovered", "incident": 1, "action": "gang",
         "mttr_s": 0.5},
        {"ts": 1.5, "kind": "goodput_incident", "incident": 1,
         "action": "gang", "downtime_s": 0.5, "detection_s": 0.05,
         "fleet_step": 5},
    ]
    by_host, _ = read_goodput_dir(interrupted_ledger)
    rep = merge_goodput(by_host, events)
    [inc] = rep["incidents"]
    # lost_steps is attributed from the ledger (step 5's re-run), not
    # from the event — the coordinator can't know it at recovery time.
    assert inc == {"incident": 1, "action": "gang", "ts": 1.5,
                   "downtime_s": 0.5, "detection_s": 0.05,
                   "fleet_step": 5, "lost_steps": 1,
                   "planned": False, "shrink": None, "ckpt": None,
                   "journal_replay_ms": None}
    assert rep["incident_downtime_s"] == pytest.approx(0.5)
    # older event files without the enriched record fall back to mttr_s
    rep2 = merge_goodput(by_host, events[:2])
    assert rep2["incidents"][0]["downtime_s"] == 0.5


def test_planned_incidents_are_flagged_and_split(interrupted_ledger):
    """Graceful-degradation fields (ISSUE 7): a drained preemption's
    incident row carries planned=true, shrink/ckpt detail passes
    through, and unplanned_downtime_s excludes the planned rows — a
    chosen restart must not read as a downtime regression."""
    events = [
        {"ts": 1.0, "kind": "detect", "incident": 1,
         "failures": [{"host": 1, "kind": "preempt", "lead_s": 30.0}]},
        {"ts": 1.4, "kind": "goodput_incident", "incident": 1,
         "action": "drain_restart", "planned": True, "downtime_s": 0.4,
         "detection_s": 0.01, "fleet_step": 5},
        {"ts": 2.0, "kind": "detect", "incident": 2,
         "failures": [{"host": 0, "kind": "crash", "rc": -9}]},
        {"ts": 2.6, "kind": "goodput_incident", "incident": 2,
         "action": "gang_restart", "planned": False, "downtime_s": 0.6,
         "detection_s": 0.02, "fleet_step": 7,
         "shrink": {"from_hosts": 2, "to_hosts": 1, "lost": [0],
                    "generation": 3}},
    ]
    by_host, _ = read_goodput_dir(interrupted_ledger)
    rep = merge_goodput(by_host, events)
    planned, unplanned = rep["incidents"]
    assert planned["planned"] is True and planned["action"] == "drain_restart"
    assert unplanned["planned"] is False
    assert unplanned["shrink"]["to_hosts"] == 1
    assert rep["incident_downtime_s"] == pytest.approx(1.0)
    assert rep["unplanned_downtime_s"] == pytest.approx(0.6)
    text = render_goodput(rep)
    assert "planned" in text  # the incident table names the split


def test_give_up_incident_still_gets_a_row(interrupted_ledger):
    """A budget-exhausted incident never writes recovered/
    goodput_incident — only detect/decide/give_up.  It must still appear
    in the report (it is the incident that ended the run), with unknown
    downtime rather than no row at all."""
    events = [
        {"ts": 1.0, "kind": "detect", "incident": 1,
         "failures": [{"host": 0, "kind": "crash", "rc": -9}]},
        {"ts": 1.5, "kind": "recovered", "incident": 1, "action": "gang",
         "mttr_s": 0.5},
        {"ts": 2.0, "kind": "detect", "incident": 2,
         "failures": [{"host": 0, "kind": "crash", "rc": -9}]},
        {"ts": 2.1, "kind": "decide", "incident": 2, "action": "give_up",
         "reason": "restart budget exhausted"},
        {"ts": 2.2, "kind": "give_up", "incident": 2, "rc": 137,
         "reason": "restart budget exhausted"},
    ]
    by_host, _ = read_goodput_dir(interrupted_ledger)
    rep = merge_goodput(by_host, events)
    assert [i["incident"] for i in rep["incidents"]] == [1, 2]
    final = rep["incidents"][1]
    assert final["action"] == "give_up"
    assert final["ts"] == 2.2
    assert final["downtime_s"] is None
    # unknown downtime must not poison the sum
    assert rep["incident_downtime_s"] == pytest.approx(0.5)
    # detect-only with no give_up/decide (observe-only incident) also rows
    rep2 = merge_goodput(by_host, events[:3])
    detect_only = rep2["incidents"][1]
    assert detect_only["incident"] == 2
    assert detect_only["action"] is None
    assert detect_only["ts"] == 2.0
    assert detect_only["downtime_s"] is None


def test_lost_steps_binned_by_time_not_step_number(tmp_path):
    # incident 1 (solo, no rewind) then incident 2 rewinding BELOW
    # incident 1's fleet_step: every re-run executes after incident 2's
    # recovery, so step-number binning would miscredit steps 4-5 to
    # incident 1 — time binning must give incident 2 all of them.
    clk = FakeClock(0.0)
    led = GoodputLedger(tmp_path, 0, clock=clk)
    for s in range(1, 11):
        _phase(led, clk, "step", 1.0, step=s)  # t=1..10
    led._f.close()  # killed
    led._f = None
    clk.advance(2.0)
    led2 = GoodputLedger(tmp_path, 0, clock=clk)
    for s in range(4, 11):
        _phase(led2, clk, "step", 1.0, step=s)  # re-runs at t=13..19
    led2.close()
    events = [
        {"ts": 5.5, "kind": "goodput_incident", "incident": 1,
         "action": "solo_restart", "downtime_s": 0.1,
         "detection_s": 0.05, "fleet_step": 5},
        {"ts": 11.5, "kind": "goodput_incident", "incident": 2,
         "action": "gang_restart", "downtime_s": 0.5,
         "detection_s": 0.05, "fleet_step": 10},
    ]
    by_host, _ = read_goodput_dir(tmp_path)
    rep = merge_goodput(by_host, events)
    assert [i["lost_steps"] for i in rep["incidents"]] == [0, 7]
    assert rep["lost_steps"] == 7


def test_adversarial_ledger_skips_and_counts(tmp_path):
    p = tmp_path / "goodput-host000.jsonl"
    p.write_text(
        json.dumps({"kind": "window", "host": 0, "t": 1.0}) + "\n"
        + json.dumps({"kind": "phase", "bucket": "step", "dur_s": 0.5,
                      "step": 1, "t": 1.5}) + "\n"
        + "{\"kind\": \"phase\", \"bucket\": \"st"  # torn tail
    )
    (tmp_path / "goodput-host001.jsonl").write_text("")  # empty host
    (tmp_path / "goodput-host002.jsonl").write_text(
        json.dumps({"kind": "phase", "bucket": "nonsense", "dur_s": 1.0,
                    "t": 2.0}) + "\n")  # malformed-only host
    by_host, skipped = read_goodput_dir(tmp_path)
    assert skipped == 1  # the torn line, counted not raised
    rep = merge_goodput(by_host, skipped_lines=skipped)
    assert rep["skipped_lines"] == 1
    assert rep["num_hosts"] >= 1
    assert rep["hosts"]["0"]["buckets"]["productive_step"] == 0.5
    assert rep["hosts"]["2"]["malformed_records"] == 1


def test_nonfinite_durations_are_malformed_not_poison(tmp_path):
    """json.loads accepts bare NaN/Infinity — one accumulated NaN would
    poison every downstream sum AND make --json output unparseable by
    strict readers, so non-finite dur_s/t must be skip-and-counted."""
    import math

    p = tmp_path / "goodput-host000.jsonl"
    p.write_text(
        json.dumps({"kind": "window", "host": 0, "t": 1.0}) + "\n"
        + '{"kind": "phase", "bucket": "step", "dur_s": NaN, '
        '"step": 1, "t": 1.2}\n'
        + '{"kind": "phase", "bucket": "ckpt", "dur_s": Infinity, '
        '"t": 1.3}\n'
        + '{"kind": "phase", "bucket": "step", "dur_s": 0.1, '
        '"step": 2, "t": NaN}\n'
        + json.dumps({"kind": "phase", "bucket": "step", "dur_s": 0.5,
                      "step": 3, "t": 1.5}) + "\n")
    by_host, skipped = read_goodput_dir(tmp_path)
    rep = merge_goodput(by_host, skipped_lines=skipped)
    host = rep["hosts"]["0"]
    assert host["malformed_records"] == 3
    assert host["buckets"]["productive_step"] == 0.5
    assert all(math.isfinite(v) for v in host["buckets"].values())
    assert math.isfinite(rep["wall_s"]) and math.isfinite(rep["accounted_s"])
    # the report must serialize under STRICT json (what jq/JS parse);
    # allow_nan=False raises on any NaN/inf that leaked through
    json.dumps(rep, allow_nan=False)


def test_goodput_report_on_missing_dirs(tmp_path):
    rep = goodput_report(tmp_path / "nope", tmp_path / "also-nope.jsonl")
    assert rep["num_hosts"] == 0 and rep["wall_s"] == 0.0


def test_read_jsonl_counting_tolerates_non_utf8(tmp_path):
    # disk corruption / binary garbage appended: skip-and-count, never
    # raise — one invalid byte must not take down the whole report.
    p = tmp_path / "goodput-host000.jsonl"
    p.write_bytes(
        json.dumps({"kind": "window", "host": 0, "t": 1.0}).encode()
        + b"\n" + b"\xff\xfe{garbage\n"
        + json.dumps({"kind": "close", "t": 2.0}).encode() + b"\n")
    recs, skipped = read_jsonl_counting(p)
    assert [r["kind"] for r in recs] == ["window", "close"]
    assert skipped == 1


def test_host_id_from_path():
    from pathlib import Path
    assert host_id_from_path(Path("/x/goodput-host007.jsonl")) == 7
    assert host_id_from_path(Path("/x/hb-host012.jsonl")) == 12
    assert host_id_from_path(Path("/x/notes.jsonl")) is None


# ---- live efficiency gauges (acceptance: fake clock, no TPU) -------------

def test_trainer_obs_exports_live_gauges_on_metrics_endpoint(tmp_path):
    from tpucfn.obs.server import ObsServer
    from tpucfn.train.trainer import TrainerObs

    clk = FakeClock(0.0)
    reg = MetricRegistry(labels={"host": "0", "role": "trainer"})
    led = GoodputLedger(tmp_path, 0, clock=clk)
    obs = TrainerObs(reg, ledger=led, clock=clk)
    for i in range(1, 4):
        with obs.data_wait(i):
            clk.advance(0.05)
        with obs.step(i):
            clk.advance(0.1)
    m = reg.varz()["metrics"]
    assert m["train_step_time_s"] == pytest.approx(0.1)
    # productive 0.2 (first step is compile) over 0.45 wall
    assert m["train_goodput_ratio"] == pytest.approx(0.2 / 0.45)
    srv = ObsServer(reg, port=0, host="127.0.0.1", role="trainer")
    try:
        body = urllib.request.urlopen(srv.url("/metrics"),
                                      timeout=5).read().decode()
    finally:
        srv.close()
    for name in ("train_step_time_s", "train_goodput_ratio"):
        assert any(line.startswith(name + "{") for line
                   in body.splitlines()), name
    # no utilization gauge: a count of operations is the benchmark's
    assert "train_mfu" not in body
    led.close()
    # and the same phases landed in the goodput ledger
    rep = goodput_report(tmp_path)
    b = rep["hosts"]["0"]["buckets"]
    assert b["compile"] == pytest.approx(0.1)
    assert b["productive_step"] == pytest.approx(0.2)
    assert b["data_wait"] == pytest.approx(0.15)
    assert rep["accounted_s"] == pytest.approx(rep["wall_s"])


def test_trainer_obs_registers_exactly_these_names():
    """The scrape interface, pinned: a dashboard or an alert names these.  A
    new metric is added here with the code; one that goes is taken out of
    whatever reads it first.  (``record_step_counters`` adds a gauge for each
    counter a loss function returns, by name, when the first one arrives.)"""
    from tpucfn.train.trainer import TrainerObs

    reg = MetricRegistry()
    TrainerObs(reg, clock=FakeClock())
    assert set(reg.varz()["metrics"]) == {
        "train_step_seconds", "train_data_wait_seconds", "train_ckpt_seconds",
        "train_steps_total", "train_last_step", "train_step_time_s",
        "train_goodput_ratio"}
    reg = MetricRegistry()
    TrainerObs(reg, prefix="learner", clock=FakeClock())
    assert all(n.startswith("learner_") for n in reg.varz()["metrics"])


# ---- compile-bucket refinement (ISSUE 6 satellite) ------------------------

def test_compile_cached_is_its_own_bucket_and_advances_max_step(tmp_path):
    # warm restart: the second incarnation's first step was served from
    # the persistent cache — it must land in compile_cached, still
    # advance the re-run horizon, and keep the sum-to-wall invariant
    clk = FakeClock()
    led = GoodputLedger(tmp_path, 0, clock=clk)
    _phase(led, clk, "compile", 1.0, step=1)
    _phase(led, clk, "step", 0.4, step=2)
    led._f.close()  # SIGKILL
    led._f = None
    clk.advance(2.0)
    led2 = GoodputLedger(tmp_path, 0, clock=clk)
    _phase(led2, clk, "compile_cached", 0.1, step=1)
    _phase(led2, clk, "step", 0.4, step=2)  # re-run: lost_work
    _phase(led2, clk, "step", 0.4, step=3)
    led2.close()
    rep = host_goodput(read_goodput_dir(tmp_path)[0][0])
    assert rep["buckets"]["compile"] == pytest.approx(1.0)
    assert rep["buckets"]["compile_cached"] == pytest.approx(0.1)
    assert rep["buckets"]["lost_work"] == pytest.approx(0.4)
    assert rep["lost_steps"] == 1
    assert rep["malformed_records"] == 0
    assert abs(rep["unaccounted_s"]) < 1e-9


@pytest.mark.parametrize("outcome, bucket", [
    ("miss", "compile"), ("hit", "compile_cached"),
    ("fetch", "compile_fetched"), (None, "compile")])
def test_the_program_outcome_decides_the_bucket(tmp_path, outcome, bucket):
    """The first step's charge follows where its program came from: a
    compile (or a first step that compiled nothing the trainer heard of)
    is ``compile``, a cache's hit ``compile_cached``, a fleet peer's
    artifact ``compile_fetched``; a program compiled outside any step (an
    eval's) decides nothing."""
    from tpucfn.train.trainer import TrainerObs

    clk = FakeClock(0.0)
    led = GoodputLedger(tmp_path, 0, clock=clk)
    obs = TrainerObs(MetricRegistry(), ledger=led, clock=clk)
    about = dict(label="train_step", lower_start=0.0, compile_start=0.1,
                 compile_end=0.9)
    obs.record_program(None, outcome="fetch", **{**about,
                                                 "label": "train_eval"})
    with obs.step(1):
        if outcome is not None:
            obs.record_program(None, outcome=outcome, **about)
        clk.advance(1.0)
    with obs.step(2):
        clk.advance(0.5)
    led.close()
    recs, _ = read_jsonl_counting(tmp_path / "goodput-host000.jsonl")
    assert [r["bucket"] for r in recs if r.get("kind") == "phase"] == [
        bucket, "step"]


# -- fleet warm start (ISSUE 13) ---------------------------------------------

def test_compile_fetched_bucket_merges_and_sums_to_wall():
    """The fetch-hit first step gets its own column; the sums-to-wall
    invariant holds with it."""
    recs = [
        {"kind": "window", "host": 0, "t": 100.0},
        {"kind": "phase", "bucket": "compile_fetched", "dur_s": 2.0,
         "step": 1, "t": 103.0, "host": 0},
        {"kind": "phase", "bucket": "step", "dur_s": 0.5, "step": 2,
         "t": 104.0, "host": 0},
        {"kind": "close", "host": 0, "t": 104.0},
    ]
    rep = host_goodput(recs)
    assert rep["buckets"]["compile_fetched"] == 2.0
    assert rep["buckets"]["compile"] == 0.0
    assert abs(rep["unaccounted_s"]) < 1e-9
    # a fetched first step still advances the re-run horizon
    assert rep["productive_steps"] == 1


def test_incident_rows_carry_journal_replay_ms():
    """ISSUE 13 satellite: the adopted coordinator's replay time rides
    the goodput_incident row into the merged report and its total."""
    by_host = {0: [
        {"kind": "window", "host": 0, "t": 10.0},
        {"kind": "phase", "bucket": "step", "dur_s": 1.0, "step": 1,
         "t": 12.0, "host": 0},
        {"kind": "close", "host": 0, "t": 12.0},
    ]}
    events = [
        {"kind": "goodput_incident", "incident": 1, "ts": 11.0,
         "action": "gang_restart", "downtime_s": 3.0,
         "detection_s": 0.05, "fleet_step": 1,
         "journal_replay_ms": 12.5},
    ]
    rep = merge_goodput(by_host, events)
    assert rep["incidents"][0]["journal_replay_ms"] == 12.5
    assert rep["journal_replay_ms"] == 12.5


def test_incident_without_replay_detail_stays_none():
    rep = merge_goodput({}, [
        {"kind": "goodput_incident", "incident": 2, "ts": 1.0,
         "action": "solo_restart", "downtime_s": 1.0}])
    assert rep["incidents"][0]["journal_replay_ms"] is None
    assert rep["journal_replay_ms"] == 0.0
