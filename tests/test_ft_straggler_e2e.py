"""End-to-end straggler-eviction drill (ISSUE 7 acceptance): the
STRAGGLER→SOLO_RESTART row is on by default but gated by the
StragglerGuard — a host that flaps (brief lag episodes that recover)
under the flap budget is never evicted, while sustained lag past the
hysteresis window earns a targeted solo restart and the run finishes
clean.

Stdlib-only workers (no jax import) so the drill measures the
eviction plane, not interpreter+XLA startup.  Own slow-marked file on
purpose: stacked multi-second drills flake on this container.
"""

import json
import os
import sys
import time
from pathlib import Path

import pytest

from tpucfn.bootstrap import EnvContract
from tpucfn.ft import (
    GangCoordinator,
    GangRestart,
    HeartbeatMonitor,
    MonitorConfig,
    RestartBudget,
    StragglerGuard,
)
from tpucfn.launch import Launcher, LocalTransport
from tpucfn.obs import MetricRegistry

pytestmark = pytest.mark.slow


def _contract(tmp_path, n=2) -> EnvContract:
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("".join("127.0.0.1:0\n" for _ in range(n)))
    return EnvContract(
        workers_path=str(hostfile), workers_count=n, worker_chip_count=1,
        coordinator="127.0.0.1:1234", host_id=0, storage=str(tmp_path),
        generation=1)


# Host 0 beats an advancing step and exits once `done` appears (or after
# the cap).  Host 1's behavior comes from FT_STRAG_MODE:
#   lag  — beat step=1 forever (sustained straggle; a relaunch beats
#          caught-up, writes `done`, exits 0)
#   flap — two brief lag episodes (shorter than the hysteresis), each
#          followed by catching up to host 0's step, then run caught-up
#          until `done`-time; never evicted, exits 0
WORKER = r"""
import json, os, pathlib, sys, time
d = os.environ['TPUCFN_FT_DIR']; h = int(os.environ['TPUCFN_HOST_ID'])
mode = os.environ['FT_STRAG_MODE']
os.makedirs(d, exist_ok=True)
fd = pathlib.Path(os.environ['FLAG_DIR'])
seq = 0
def beat(step):
    global seq
    seq += 1
    with open(f'{d}/hb-host{h:03d}.jsonl', 'a') as f:
        f.write(json.dumps({'host_id': h, 'pid': os.getpid(),
                            'step': step, 't': time.time(),
                            'seq': seq}) + '\n')
def h0_step():
    try:
        lines = open(f'{d}/hb-host000.jsonl').read().splitlines()
        return json.loads(lines[-1])['step']
    except Exception:
        return 1
if h == 0:
    t_end = time.time() + 20
    i = 0
    while time.time() < t_end:
        i += 1
        beat(100 + i)
        if (fd / 'done').exists():
            sys.exit(0)
        time.sleep(0.05)
    sys.exit(1)
# -- host 1 --
if (fd / 'second_1').exists():
    beat(h0_step())          # relaunched: caught up
    (fd / 'done').write_text('x')
    sys.exit(0)
fd.joinpath('second_1').write_text('x')
if mode == 'lag':
    t_end = time.time() + 20
    while time.time() < t_end:
        beat(1)
        time.sleep(0.05)
    sys.exit(1)
# flap mode: two sub-hysteresis lag episodes, recovery in between,
# then a caught-up tail; host 1 itself ends the run (it was never
# evicted, so no relaunch exists to do it)
for cycle in range(2):
    t_end = time.time() + 0.35
    while time.time() < t_end:
        beat(1)
        time.sleep(0.05)
    t_end = time.time() + 0.6
    while time.time() < t_end:
        beat(h0_step())
        time.sleep(0.05)
t_end = time.time() + 0.3
while time.time() < t_end:
    beat(h0_step())
    time.sleep(0.05)
(fd / 'done').write_text('x')
sys.exit(0)
"""


def _run(tmp_path, mode):
    ft_dir = tmp_path / "ft"
    os.environ["FLAG_DIR"] = str(tmp_path)
    os.environ["FT_STRAG_MODE"] = mode
    try:
        registry = MetricRegistry()
        launcher = Launcher(_contract(tmp_path), LocalTransport(),
                            ft_dir=str(ft_dir), ft_heartbeat_s=0.05)
        coord = GangCoordinator(
            launcher, [sys.executable, "-c", WORKER],
            policy=GangRestart(RestartBudget(2)),
            monitor=HeartbeatMonitor(
                ft_dir, expected_hosts=2,
                config=MonitorConfig(interval_s=0.05,
                                     startup_grace_s=10.0,
                                     straggler_step_lag=20)),
            registry=registry, ft_dir=ft_dir, poll_interval=0.01,
            term_grace_s=0.5,
            straggler_guard=StragglerGuard(hysteresis_s=0.8,
                                           flap_budget=3))
        t0 = time.monotonic()
        rc = coord.run()
        wall = time.monotonic() - t0
    finally:
        del os.environ["FLAG_DIR"], os.environ["FT_STRAG_MODE"]
    events = [json.loads(s) for s in
              (ft_dir / "events.jsonl").read_text().splitlines()]
    return rc, wall, registry.varz()["metrics"], events


def test_sustained_lag_past_hysteresis_is_evicted(tmp_path):
    """In `done`-gated mode, only the eviction lets the run finish: the
    straggler's relaunch is what writes `done` — rc 0 proves the
    eviction happened AND the solo restart rejoined the gang."""
    rc, wall, m, events = _run(tmp_path, "lag")
    assert rc == 0
    assert wall < 15
    assert m["ft_straggler_evictions_total"] == 1
    assert m["ft_solo_restarts_total"] == 1
    assert m["ft_gang_restarts_total"] == 0
    detect = next(e for e in events if e["kind"] == "detect")
    assert detect["failures"][0]["kind"] == "straggler"
    assert detect["failures"][0]["host"] == 1
    decide = next(e for e in events if e["kind"] == "decide")
    assert decide["action"] == "solo_restart" and decide["hosts"] == [1]
    solo = next(e for e in events if e["kind"] == "solo_launch")
    assert solo["host"] == 1


def test_flap_under_budget_is_never_evicted(tmp_path):
    """Two brief lag episodes (0.35s each, hysteresis 0.8s, budget 3):
    flaps are tolerated, nothing restarts, both hosts exit clean."""
    rc, wall, m, events = _run(tmp_path, "flap")
    assert rc == 0
    assert m["ft_straggler_evictions_total"] == 0
    assert m["ft_solo_restarts_total"] == 0
    assert m["ft_restarts_total"] == 0
    assert not any(e["kind"] == "detect" for e in events), \
        "a flap under the budget must not even open an incident"
