"""End-to-end elastic-shrink drill (ISSUE 7 acceptance): a preemption
notice drains host 1 cleanly (force-save at the drain boundary), then a
chaos ``lose_host`` takes it away for good — the relaunch cannot
re-acquire it, so the coordinator re-converges the ``EnvContract`` at
N-1 with a new generation and the one-host gang resumes from the
force-saved step and finishes, its loss curve bit-identical to the
deterministic trajectory.

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

TOTAL_STEPS = 30
CKPT_EVERY = 10
# The two triggers must sit MORE than one observe quantum apart (fleet
# step advances ~2 steps per throttled observe): close triggers can
# fire in the same chaos tick and the loss lands mid-drain instead of
# against the relaunched gang.  With margin 4 the drain target tops out
# at ~NOTICE+2+4 < LOSE only barely — the lose then fires off the
# drained incarnation's final beats (or the relaunched gang's first),
# always AFTER the drain completed.
NOTICE_AT_STEP = 12
LOSE_AT_STEP = 17


def _contract(tmp_path, n) -> EnvContract:
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("".join("127.0.0.1:0\n" for _ in range(n)))
    return EnvContract(
        workers_path=str(hostfile), workers_count=n, worker_chip_count=1,
        coordinator="127.0.0.1:1234", host_id=0, storage=str(tmp_path),
        generation=1)


def test_lose_host_shrinks_and_resumes_from_force_save(tmp_path):
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
    # Notice first (clean drain + force-save), THEN the host is gone for
    # good: the post-drain relaunch is killed by lose_host (the old
    # incarnation's final beats already satisfy at_step) and the next
    # recovery must shrink instead of relaunching a revoked machine.
    chaos = ChaosSpec(events=(
        ChaosEvent(action="preempt_notice", at_step=NOTICE_AT_STEP,
                   host=1, duration_s=60.0),
        ChaosEvent(action="lose_host", at_step=LOSE_AT_STEP, host=1),
    ))
    coord = GangCoordinator(
        launcher, [sys.executable, WORKER],
        policy=GangRestart(RestartBudget(1)), monitor=monitor,
        registry=registry, ft_dir=ft_dir, ckpt_dir=run_dir / "ckpt",
        poll_interval=0.02, term_grace_s=1.0, chaos=chaos,
        drain_step_margin=4)
    rc = coord.run()
    assert rc == 0, "the shrunk gang must finish clean"
    assert coord.chaos.done()

    m = registry.varz()["metrics"]
    assert m["ft_preempt_drains_total"] == 1
    assert m["ft_shrinks_total"] == 1
    assert m["ft_gang_restarts_total"] == 1  # the shrink relaunch
    assert m["supervisor_gang_hosts"] == 1   # running at N-1

    events = [json.loads(s) for s in
              (ft_dir / "events.jsonl").read_text().splitlines()]
    drain = next(e for e in events if e["kind"] == "drain")
    target = drain["step"]
    assert any(e["kind"] == "host_lost" and e["host"] == 1
               for e in events)
    shrink = next(e for e in events if e["kind"] == "shrink")
    assert shrink["from_hosts"] == 2 and shrink["to_hosts"] == 1
    assert shrink["lost"] == [1]
    assert shrink["generation"] == 2, "contract generation bumped"
    # the coordinator's live contract is the shrunk one
    assert coord.launcher.contract.workers_count == 1
    assert coord.launcher.contract.generation == 2
    gp = [e for e in events if e["kind"] == "goodput_incident"]
    assert gp[0]["planned"] is True                 # the drain
    assert gp[1]["shrink"]["to_hosts"] == 1         # the shrink restart
    assert gp[1]["planned"] is False

    # -- host 0's loss curve: drained at the boundary, resumed from the
    # force-saved step after the shrink, ran to the end, every step's w
    # bit-identical to the deterministic trajectory -------------------
    rows = [json.loads(s) for s in
            (run_dir / "losses-host000.jsonl").read_text().splitlines()]
    by_step = {}
    for r in rows:  # later incarnations re-run steps; last write wins
        by_step[r["step"]] = r
    assert max(by_step) == TOTAL_STEPS
    w = 10.0
    for step in range(1, TOTAL_STEPS + 1):
        w = 0.9 * w + 0.1
        assert by_step[step]["w"] == w, f"trajectory diverged at {step}"
    pids = list(dict.fromkeys(r["pid"] for r in rows))
    assert len(pids) >= 2, "host 0 was relaunched at least once"
    final = [r for r in rows if r["pid"] == pids[-1]]
    # continuing from the force-saved drain boundary, not from step 0
    assert final[0]["step"] > 1
    assert final[0]["step"] <= target + 1
    # the lost host stopped within a few steps of the drain boundary
    # (its post-drain relaunch was killed almost immediately)
    rows1 = [json.loads(s) for s in
             (run_dir / "losses-host001.jsonl").read_text().splitlines()]
    assert max(r["step"] for r in rows1) <= target + 4
