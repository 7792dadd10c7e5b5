"""End-to-end preemption-drain drill (ISSUE 7 acceptance): a chaos
``preempt_notice`` mid-run makes the coordinator drain the gang — every
host runs to one converged step boundary, force-saves, exits clean —
and relaunch it as a PLANNED restart: ``lost_work == 0`` in the goodput
report, ``planned=true`` on the incident row, and zero restart budget
consumed.

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
from tpucfn.obs.goodput import goodput_report

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent
WORKER = str(REPO / "tests" / "ft_e2e_worker.py")

TOTAL_STEPS = 40
CKPT_EVERY = 10
NOTICE_AT_STEP = 18


def _contract(tmp_path, n) -> EnvContract:
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("".join("127.0.0.1:0\n" for _ in range(n)))
    return EnvContract(
        workers_path=str(hostfile), workers_count=n, worker_chip_count=1,
        coordinator="127.0.0.1:1234", host_id=0, storage=str(tmp_path),
        generation=1)


def _losses(run_dir, host=0) -> list[dict]:
    p = run_dir / f"losses-host{host:03d}.jsonl"
    return [json.loads(s) for s in p.read_text().splitlines() if s.strip()]


def test_preempt_notice_drains_with_zero_lost_work(tmp_path):
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
    chaos = ChaosSpec(events=(
        ChaosEvent(action="preempt_notice", at_step=NOTICE_AT_STEP,
                   host=0, duration_s=60.0),))
    coord = GangCoordinator(
        launcher, [sys.executable, WORKER],
        # ZERO budget: a drained preemption must not need a restart slot
        policy=GangRestart(RestartBudget(0)), monitor=monitor,
        registry=registry, ft_dir=ft_dir, ckpt_dir=run_dir / "ckpt",
        poll_interval=0.02, term_grace_s=1.0, chaos=chaos,
        # generous margin: the fleet step is observe-throttled, so the
        # target must sit past any host's true position at drain time
        drain_step_margin=4)
    rc = coord.run()
    assert rc == 0, "planned drain + relaunch must finish clean"
    assert coord.chaos.done()

    m = registry.varz()["metrics"]
    assert m["ft_preempt_drains_total"] == 1
    assert m["ft_planned_restarts_total"] == 1
    assert m["ft_restarts_total"] == 0, "no budget slot consumed"
    assert m["ft_planned_mttr_seconds"]["count"] == 1

    events = [json.loads(s) for s in
              (ft_dir / "events.jsonl").read_text().splitlines()]
    kinds = [e["kind"] for e in events]
    assert "drain" in kinds and "done" in kinds
    detect = next(e for e in events if e["kind"] == "detect")
    assert detect["failures"][0]["kind"] == "preempt"
    assert detect["failures"][0]["lead_s"] == 60.0
    drain = next(e for e in events if e["kind"] == "drain")
    target = drain["step"]
    assert target is not None and target >= NOTICE_AT_STEP
    recovered = next(e for e in events if e["kind"] == "recovered")
    assert recovered["planned"] is True
    assert recovered["escalated"] == 0, "every rank drained cleanly"
    assert recovered["dirty_exits"] == []

    # -- both hosts stopped AT the target and resumed right after it ---
    for host in (0, 1):
        rows = _losses(run_dir, host)
        pids = list(dict.fromkeys(r["pid"] for r in rows))
        assert len(pids) == 2, "one planned restart of each host"
        first = [r for r in rows if r["pid"] == pids[0]]
        resumed = [r for r in rows if r["pid"] == pids[1]]
        assert first[-1]["step"] == target, "drained exactly at the target"
        assert resumed[0]["step"] == target + 1, "zero re-executed steps"
        assert resumed[-1]["step"] == TOTAL_STEPS
        # no step was paid for twice
        steps = [r["step"] for r in rows]
        assert len(steps) == len(set(steps))

    # -- the goodput plane agrees: planned incident, zero lost work ----
    report = goodput_report(run_dir / "goodput", ft_dir / "events.jsonl")
    assert report["lost_work_s"] == 0.0
    assert report["lost_steps"] == 0
    [inc] = report["incidents"]
    assert inc["planned"] is True
    assert inc["action"] == "drain_restart"
    assert report["unplanned_downtime_s"] == 0.0
    assert report["incident_downtime_s"] > 0  # the drain took real time
    # budget untouched, visible to `tpucfn ft status`
    snap = json.loads((ft_dir / "supervisor.json").read_text())
    assert snap["budget"] == {"max_restarts": 0, "used": 0}
