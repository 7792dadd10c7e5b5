"""End-to-end smoke of the minimum slice (SURVEY.md §7.3): the bundled
CIFAR-10 example trains on 8 fake devices in a subprocess, checkpoints,
and resumes — the convergence-smoke analogue of the reference's "stack
reaches CREATE_COMPLETE and the CIFAR-10 example converges" manual test.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run_example(run_dir, steps, resume=False, extra=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [
        sys.executable, str(REPO / "examples" / "cifar10_resnet20.py"),
        "--run-dir", str(run_dir),
        "--batch-size", "64",
        "--steps", str(steps),
        "--num-examples", "256",
        "--ckpt-every", "5",
        "--log-every", "5",
    ] + (["--resume"] if resume else []) + list(extra)
    return subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=600)


def test_cifar10_example_end_to_end(tmp_path):
    r = _run_example(tmp_path, steps=10)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "items/sec" in r.stdout

    # metrics were logged as JSONL with loss/accuracy/step_time, plus the
    # one-shot time_to_first_step record (SURVEY.md §7.4 item 6)
    logs = list((tmp_path / "logs").glob("*.jsonl"))
    assert logs, r.stdout
    records = [json.loads(line) for line in logs[0].read_text().splitlines()]
    assert any(rec["step"] == 10 for rec in records)
    assert any("time_to_first_step" in rec for rec in records)
    loss_recs = [rec for rec in records if "time_to_first_step" not in rec]
    assert loss_recs and all("loss" in rec for rec in loss_recs)

    # checkpoints exist
    assert (tmp_path / "ckpt").exists()

    # restart implies resume: a plain relaunch (no --resume) continues
    # from step 10 rather than retraining from 0 (SURVEY.md §5 failure row)
    r2 = _run_example(tmp_path, steps=14)
    assert r2.returncode == 0, f"stdout:\n{r2.stdout}\nstderr:\n{r2.stderr}"
    assert "resumed from step 10" in r2.stdout
    m = re.search(r"final: step=(\d+)", r2.stdout)
    assert m and int(m.group(1)) == 14

    # --fresh opts out and retrains from step 0
    r3 = _run_example(tmp_path, steps=3, extra=("--fresh",))
    assert r3.returncode == 0, f"stdout:\n{r3.stdout}\nstderr:\n{r3.stderr}"
    assert "resumed" not in r3.stdout


def test_cifar10_example_stop_after_keeps_budget(tmp_path):
    """--stop-after halts execution without redefining the budget: the
    first leg stops at 4 of a 12-step budget, the relaunch resumes at 4
    and runs to the SAME 12-step budget (an interruption must not change
    the LR schedule — using --steps as the cap would anneal a --cosine
    schedule to zero by the interruption point; observed degrading eval
    on the full accuracy run)."""
    r = _run_example(tmp_path, steps=12, extra=("--stop-after", "4", "--cosine"))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    m = re.search(r"final: step=(\d+)", r.stdout)
    assert m and int(m.group(1)) == 4

    r2 = _run_example(tmp_path, steps=12, extra=("--cosine",))
    assert r2.returncode == 0, f"stdout:\n{r2.stdout}\nstderr:\n{r2.stderr}"
    assert "resumed from step 4" in r2.stdout
    m = re.search(r"final: step=(\d+)", r2.stdout)
    assert m and int(m.group(1)) == 12


def test_cifar10_example_fsdp_mode(tmp_path):
    r = _run_example(tmp_path, steps=4, extra=("--fsdp", "2"))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_cifar10_example_eval_split(tmp_path):
    r = _run_example(tmp_path, steps=6, extra=("--eval-every", "3"))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    logs = list((tmp_path / "logs").glob("*.jsonl"))
    records = [json.loads(line) for line in logs[0].read_text().splitlines()]
    eval_recs = [rec for rec in records if "eval_accuracy" in rec]
    assert eval_recs, records
    assert all("eval_loss" in rec for rec in eval_recs)
