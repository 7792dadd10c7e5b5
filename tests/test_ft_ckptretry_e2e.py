"""End-to-end checkpoint-corruption retry drill (ISSUE 7 acceptance):
chaos corrupts the latest finalized checkpoint and kills host 0 in the
same tick; the relaunched gang's restore fails with the distinguishable
``RESTORE_FAILED_RC``, and the coordinator — instead of crash-looping
the corrupt artifact through the budget into give_up — quarantines and
blacklists the bad step and relaunches to resume from the PREVIOUS
finalized step, finishing with the correct trajectory.

Own slow-marked file on purpose: stacked multi-second drills flake on
this container.
"""

import json
import os
import sys
from pathlib import Path

import pytest

from tpucfn.bootstrap import EnvContract
from tpucfn.ft import (
    ChaosEvent,
    ChaosSpec,
    GangCoordinator,
    GangRestart,
    HeartbeatMonitor,
    MonitorConfig,
    RestartBudget,
)
from tpucfn.launch import Launcher, LocalTransport
from tpucfn.obs import MetricRegistry

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent
WORKER = str(REPO / "tests" / "ft_e2e_worker.py")

TOTAL_STEPS = 40
CKPT_EVERY = 10
KILL_AT_STEP = 25
BAD_STEP = 20      # the latest finalized checkpoint at the kill point
PREV_STEP = 10     # where the retry must resume from


def _contract(tmp_path, n) -> EnvContract:
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("".join("127.0.0.1:0\n" for _ in range(n)))
    return EnvContract(
        workers_path=str(hostfile), workers_count=n, worker_chip_count=1,
        coordinator="127.0.0.1:1234", host_id=0, storage=str(tmp_path),
        generation=1)


def test_corrupt_latest_retries_from_previous_without_give_up(tmp_path):
    run_dir = tmp_path / "run"
    ft_dir = run_dir / "ft"
    run_dir.mkdir()
    os.environ.update({
        "FT_E2E_RUN_DIR": str(run_dir),
        "FT_E2E_TOTAL_STEPS": str(TOTAL_STEPS),
        "FT_E2E_CKPT_EVERY": str(CKPT_EVERY),
        "FT_E2E_STEP_SLEEP": "0.05",
        "PYTHONPATH": str(REPO) + os.pathsep + os.environ.get(
            "PYTHONPATH", ""),
    })
    launcher = Launcher(_contract(tmp_path, 2), LocalTransport(),
                        ft_dir=str(ft_dir), ft_heartbeat_s=0.2)
    registry = MetricRegistry()
    monitor = HeartbeatMonitor(
        ft_dir, expected_hosts=2,
        config=MonitorConfig(interval_s=0.2, startup_grace_s=120.0))
    # Same tick, schedule order: corrupt the (finalized) step-20
    # checkpoint FIRST, then kill host 0 — the gang restart then walks
    # straight into the corrupt restore.
    chaos = ChaosSpec(events=(
        ChaosEvent(action="corrupt_ckpt", at_step=KILL_AT_STEP,
                   step=BAD_STEP),
        ChaosEvent(action="kill", at_step=KILL_AT_STEP, host=0),
    ))
    coord = GangCoordinator(
        launcher, [sys.executable, WORKER],
        # budget 1 covers the kill; the ckpt retry must not need more
        policy=GangRestart(RestartBudget(1)), monitor=monitor,
        registry=registry, ft_dir=ft_dir, ckpt_dir=run_dir / "ckpt",
        poll_interval=0.02, term_grace_s=1.0, chaos=chaos)
    rc = coord.run()
    assert rc == 0, "retry-from-previous must finish clean, not give_up"
    assert coord.chaos.done()

    m = registry.varz()["metrics"]
    assert m["ft_ckpt_retries_total"] == 1
    assert m["ft_give_ups_total"] == 0
    assert m["ft_gang_restarts_total"] == 2  # the kill + the retry

    events = [json.loads(s) for s in
              (ft_dir / "events.jsonl").read_text().splitlines()]
    assert any(e["kind"] == "chaos_ckpt_corrupted" and
               e["path"] and f"/{BAD_STEP}/" in e["path"] for e in events)
    retry = next(e for e in events if e["kind"] == "ckpt_retry")
    assert retry["bad_step"] == BAD_STEP
    assert retry["retry_from"] == PREV_STEP
    assert retry["blacklist"] == [BAD_STEP]
    gp = [e for e in events if e["kind"] == "goodput_incident"]
    assert gp[-1]["action"] == "ckpt_retry"
    assert gp[-1]["ckpt"] == {"bad_step": BAD_STEP,
                              "retry_from": PREV_STEP}

    # the corrupt artifact was quarantined for forensics (and the step
    # number freed — the re-run writes a FRESH step-20 below)
    assert (run_dir / "ckpt" / "corrupt" / str(BAD_STEP)).is_dir()

    # -- the trajectory: resumed from step 10, re-ran to the end,
    # bit-identical w at every step ------------------------------------
    rows = [json.loads(s) for s in
            (run_dir / "losses-host000.jsonl").read_text().splitlines()]
    pids = list(dict.fromkeys(r["pid"] for r in rows))
    # two incarnations wrote rows: the initial run and the retry run —
    # the failed-restore incarnation died before its first step
    assert len(pids) == 2
    final = [r for r in rows if r["pid"] == pids[-1]]
    assert final[0]["step"] == PREV_STEP + 1, \
        "the retry resumed from the PREVIOUS finalized step"
    assert final[-1]["step"] == TOTAL_STEPS
    by_step = {}
    for r in rows:
        by_step[r["step"]] = r
    w = 10.0
    for step in range(1, TOTAL_STEPS + 1):
        w = 0.9 * w + 0.1
        assert by_step[step]["w"] == w, f"trajectory diverged at {step}"
    # a fresh, uncorrupted step-20 checkpoint exists again (the re-run
    # saved into the freed step number)
    assert (run_dir / "ckpt" / str(BAD_STEP)).is_dir()
