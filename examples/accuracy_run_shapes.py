#!/usr/bin/env python
"""End-to-end train-to-accuracy run (the framework closing its own loop).

The reference's de-facto integration test was "the stack comes up and
CIFAR-10 *converges*" (SURVEY.md §4). Zero egress means no real CIFAR-10
in this environment, so the documented substitution is the procgen-shapes
dataset (tpucfn/data/shapes.py): 10 shape classes whose ONLY class signal
is geometry — a linear probe on raw pixels sits near chance (measured
below), while ResNet-20 is expected to reach >=90% eval accuracy.

This driver runs the full user path, every hop through the framework's
own surfaces (no bespoke training code):

  1. generate PNG image trees (train/eval) — "the user's dataset on disk"
  2. ``tpucfn convert-dataset --kind image-tree`` -> encoded tpurecord shards
  3. ``tpucfn create-stack`` (fake control plane, cpu-1)
  4. ``tpucfn launch examples/cifar10_resnet20.py`` — multi-epoch train
     with --eval-every, STOPPED early by a step cap (simulated
     interruption), checkpointing throughout
  5. relaunch with the full budget — restart-implies-resume picks up the
     checkpoint and trains to the end (final eval logged)
  6. relaunch once more — resumes at the final step, re-runs eval on the
     restored weights; accuracy must match step 5's final eval
  7. gates: final eval_accuracy >= 0.90 AND |resume re-eval - final| tiny
  8. writes ACCURACY_RUN.md + copies the metrics JSONL into runs/

Run from the repo root: ``python examples/accuracy_run_shapes.py``
(takes ~1-2 h on a 1-core CPU host; all subprocesses run on a scrubbed
8-fake-device CPU backend, whatever accelerator the host has).
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# Import the env scrub by file path (this process must never import jax —
# same rule as __graft_entry__).
_spec = importlib.util.spec_from_file_location(
    "_tpucfn_env", REPO / "tpucfn" / "utils" / "env.py")
_envmod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_envmod)

N_TRAIN = int(os.environ.get("TPUCFN_ACC_TRAIN", "10000"))
N_EVAL = int(os.environ.get("TPUCFN_ACC_EVAL", "2000"))
EPOCHS = int(os.environ.get("TPUCFN_ACC_EPOCHS", "30"))
BATCH = int(os.environ.get("TPUCFN_ACC_BATCH", "128"))
LR = float(os.environ.get("TPUCFN_ACC_LR", "0.15"))
ACC_GATE = float(os.environ.get("TPUCFN_ACC_GATE", "0.90"))


def _env() -> dict[str, str]:
    env = _envmod.scrub_accelerator_env(os.environ, n_devices=8)
    env["PYTHONPATH"] = str(REPO) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def run(argv: list[str], **kw) -> subprocess.CompletedProcess:
    print(f"+ {' '.join(str(a) for a in argv)}", flush=True)
    return subprocess.run([str(a) for a in argv], env=_env(), cwd=REPO,
                          text=True, capture_output=True, **kw)


def must(r: subprocess.CompletedProcess, what: str) -> str:
    if r.returncode != 0:
        sys.stderr.write(r.stdout[-4000:] + "\n" + r.stderr[-4000:])
        raise SystemExit(f"{what} failed rc={r.returncode}")
    return r.stdout


def cli(*argv, state: Path) -> str:
    return must(run([sys.executable, "-m", "tpucfn.cli",
                     "--state-dir", state, *argv]),
                f"tpucfn {argv[0]}")


def read_metrics(run_dir: Path) -> list[dict]:
    rows = []
    for p in sorted((run_dir / "logs").glob("*.jsonl")):
        for line in p.read_text().splitlines():
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return rows


def eval_rows(rows: list[dict]) -> list[tuple[int, float]]:
    """Eval points in CHRONOLOGICAL (file) order — relaunches append, so
    the last row is always the newest measurement even when a resumed
    leg re-evals at an already-seen step."""
    return [(r["step"], r["eval_accuracy"]) for r in rows
            if "eval_accuracy" in r]


def linear_probe(work: Path) -> float:
    """Ridge-regression probe on raw pixels of the SAME staged shards —
    the documented non-linear-separability evidence."""
    code = f"""
import numpy as np, jax
jax.config.update("jax_platforms", "cpu")
from tpucfn.data import ShardedDataset, decode_transform
import glob
def load(split):
    X, y = [], []
    paths = sorted(glob.glob(r"{work}/shards/" + split + "/*.tpurec"))
    assert paths, "no shards staged for " + split
    ds = ShardedDataset(paths, batch_size_per_process=256, shuffle=False,
                        drop_remainder=False, transform=decode_transform(),
                        process_index=0, process_count=1)
    for b in ds.epoch(0):
        X += [np.asarray(img, np.float32).reshape(-1) for img in b["image"]]
        y += list(b["label"])
    return np.stack(X) / 255.0, np.asarray(y)
Xtr, ytr = load("train"); Xte, yte = load("eval")
Xtr, ytr = Xtr[:6000], ytr[:6000]
W = np.linalg.solve(Xtr.T @ Xtr + 10.0 * np.eye(Xtr.shape[1]), Xtr.T @ np.eye(10)[ytr])
print("PROBE", float((np.argmax(Xte @ W, 1) == yte).mean()))
"""
    out = must(run([sys.executable, "-c", code]), "linear probe")
    for line in out.splitlines():
        if line.startswith("PROBE"):
            return float(line.split()[1])
    raise SystemExit("probe printed no result")


def main() -> int:
    t0 = time.time()
    work = Path(os.environ.get("TPUCFN_ACC_WORK", "/tmp/tpucfn-accuracy"))
    state = work / "state"
    run_dir = work / "run"
    work.mkdir(parents=True, exist_ok=True)

    # 1. the "user's dataset": PNG trees on disk
    if not (work / "tree" / "train").exists():
        must(run([sys.executable, "-c",
                  "from tpucfn.data.shapes import write_shapes_image_tree as w;"
                  f"w(r'{work}/tree/train', {N_TRAIN}, seed=0);"
                  f"w(r'{work}/tree/eval', {N_EVAL}, seed=1)"]),
             "tree generation")

    # 2. convert: image tree -> encoded tpurecord shards
    for split in ("train", "eval"):
        if not (work / "shards" / split).exists():
            cli("convert-dataset", "--kind", "image-tree",
                "--src", work / "tree" / split,
                "--out", work / "shards" / split,
                "--num-shards", "8", state=state)

    probe_acc = linear_probe(work)
    print(f"linear probe on raw pixels: {probe_acc:.3f}", flush=True)

    # 3. stack up (fake control plane — no cloud in this environment)
    cli("create-stack", "--name", "acc", "--accelerator", "cpu-1",
        "--storage", work / "efs", state=state)

    total_steps = (N_TRAIN // BATCH) * EPOCHS
    train_argv = [
        sys.executable, str(REPO / "examples" / "cifar10_resnet20.py"),
        "--data-url", work / "shards" / "train",
        "--eval-url", work / "shards" / "eval",
        "--augment", "--cosine", "--lr", LR, "--batch-size", BATCH,
        "--num-epochs", EPOCHS, "--eval-every", "200",
        "--ckpt-every", "100", "--loader-workers", "2",
        "--log-every", "50", "--run-dir", run_dir,
    ]

    # 4. first leg: interrupted at ~half the budget. --stop-after halts
    # execution WITHOUT redefining the budget, so the cosine schedule is
    # identical across legs (a real preemption does not change the LR
    # plan — using --steps here would anneal to zero by the cap and the
    # resumed leg's restored LR would kick the model out of its minimum;
    # observed exactly that on the first full run: eval 99.6% at the
    # interruption, 81.9% twenty steps after resume).
    half = total_steps // 2
    out1 = cli("launch", "--name", "acc", "--",
               *train_argv, "--stop-after", str(half), state=state)
    print(out1[-600:], flush=True)

    # 5. relaunch, full budget: restart-implies-resume from the checkpoint
    out2 = cli("launch", "--name", "acc", "--", *train_argv, state=state)
    print(out2[-600:], flush=True)
    assert "resumed from step" in out2, "second leg did not resume"
    curve = eval_rows(read_metrics(run_dir))
    if not curve:
        raise SystemExit("no eval_accuracy rows logged")
    final_step, final_acc = curve[-1]

    # 6. third leg: resumes at the final step, re-evals restored weights
    out3 = cli("launch", "--name", "acc", "--", *train_argv, state=state)
    assert "resumed from step" in out3, "third leg did not resume"
    curve3 = eval_rows(read_metrics(run_dir))
    re_step, re_acc = curve3[-1]
    assert re_step == final_step, (re_step, final_step)

    cli("delete", "--name", "acc", state=state)

    # 7. gates
    resume_delta = abs(re_acc - final_acc)
    ok = final_acc >= ACC_GATE and resume_delta < 5e-3
    mins = (time.time() - t0) / 60

    # 8. report + committed metrics artifact
    runs = REPO / "runs"
    runs.mkdir(exist_ok=True)
    merged = runs / "accuracy_shapes_metrics.jsonl"
    with merged.open("w") as f:
        for r in read_metrics(run_dir):
            f.write(json.dumps(r) + "\n")
    md = REPO / "ACCURACY_RUN.md"
    lines = [
        "# End-to-end accuracy run — procgen-shapes, ResNet-20",
        "",
        f"Date: {time.strftime('%Y-%m-%d %H:%M UTC', time.gmtime())} · "
        f"wall clock {mins:.0f} min · host: 1-core CPU, 8 fake JAX devices "
        "(zero-egress environment; see substitution note)",
        "",
        "## Substitution note (read first)",
        "",
        "The reference's integration test trains REAL CIFAR-10 staged from",
        "S3 (SURVEY.md §4). This build environment has **zero egress** — no",
        "public dataset can be downloaded — so the run substitutes the",
        "procedurally generated **procgen-shapes** dataset",
        "(`tpucfn/data/shapes.py`): 10 shape classes, class signal carried",
        "by geometry only (random position/scale/rotation/colors/gradient",
        "background/noise). It is honestly hard in the sense that matters:",
        f"a ridge linear probe on raw pixels scores **{probe_acc:.1%}**",
        "(chance = 10%), so the accuracy below is earned by representation",
        "learning, not template matching.",
        "",
        "## The path exercised (every hop a framework surface)",
        "",
        "PNG image tree → `tpucfn convert-dataset --kind image-tree` →",
        "encoded tpurecord shards → `tpucfn create-stack` (fake control",
        "plane) → `tpucfn launch examples/cifar10_resnet20.py` (streaming",
        "ShardedDataset, host decode + pad-crop/mirror augmentation, 2",
        "decode threads, warmup-cosine SGD, Orbax checkpoints every 100",
        "steps, eval every 200) → **interrupted first leg** (--stop-after",
        "at half budget — halts execution without changing the LR",
        "schedule, like a real preemption) → relaunch auto-resumes from the",
        "checkpoint → trains to the full budget → relaunch again re-evals",
        "the restored weights.",
        "",
        "## Config",
        "",
        f"- train/eval examples: {N_TRAIN}/{N_EVAL} (balanced, 10 classes)",
        f"- ResNet-20 (cifar stem), global batch {BATCH}, {EPOCHS} epochs "
        f"= {total_steps} steps, warmup-cosine peak lr {LR}",
        "",
        "## Results",
        "",
        "| gate | value | pass |",
        "|---|---|---|",
        f"| final eval accuracy (step {final_step}) | **{final_acc:.4f}** "
        f"| {'YES' if final_acc >= ACC_GATE else 'NO'} (gate {ACC_GATE}) |",
        f"| resume re-eval == final (step {re_step}) | Δ={resume_delta:.2e} "
        f"| {'YES' if resume_delta < 5e-3 else 'NO'} |",
        f"| linear probe (hardness) | {probe_acc:.4f} | "
        "near chance as required |",
        "",
        "## Eval curve",
        "",
        "| step | eval accuracy |",
        "|---|---|",
    ]
    lines += [f"| {s} | {a:.4f} |" for s, a in curve]
    lines += [
        "",
        f"Raw metrics: `runs/{merged.name}` (per-step train loss/accuracy, "
        "step_time, time_to_first_step, eval rows).",
        "",
        "Reproduce: `python examples/accuracy_run_shapes.py` from the repo "
        "root (env knobs TPUCFN_ACC_{TRAIN,EVAL,EPOCHS,BATCH,LR,GATE}).",
    ]
    md.write_text("\n".join(lines) + "\n")
    print(f"final eval accuracy {final_acc:.4f} (gate {ACC_GATE}) "
          f"resume delta {resume_delta:.2e} -> {'PASS' if ok else 'FAIL'}",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
