"""The harness end to end at tiny sizes on the CPU, through its rehearsal
option, for every cell file; and with the timed path broken underneath."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
_spec = importlib.util.spec_from_file_location(
    "_tpucfn_env", ROOT / "tpucfn" / "utils" / "env.py")
_env = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_env)

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
DEVICE_METRICS = {m["name"] for k in ("end_to_end", "per_layer") for m in
                  json.loads((ROOT / "BENCHMARK.json").read_text())[k]
                  if m["source"] in ("device_trace", "host_clock")}


def run(args, cwd=ROOT, script=("-m", "benchmark.run"), program=None):
    env = _env.scrub_accelerator_env(os.environ, n_devices=1)
    env["PYTHONPATH"] = os.pathsep.join(map(str, filter(None, [cwd, program])))
    return subprocess.run([sys.executable, *script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


FAMILY = '''
from benchmark.families.linear_ref import reference  # noqa: F401


def build(config, mix, mesh, init_fn):
    import jax.numpy as jnp
    import optax

    from tpucfn.parallel import dense_rules
    from tpucfn.train import Trainer

    def loss_fn(params, mstate, batch, rng):
        out = batch["x"] @ params["w"]["kernel"]
        loss = jnp.mean(jnp.square(out - batch["y"]))
        return loss, ({"accuracy": jnp.zeros(())}, mstate)

    trainer = Trainer(mesh, dense_rules(fsdp=False), loss_fn,
                      optax.adafactor(config["job"]["lr"]), init_fn)
    return trainer, mix["shape"]["batch"]


def step_flops(model, shape):
    return 3 * 2.0 * model["in_features"] * model["out_features"] * shape["batch"]
'''
REFERENCE = '''
import jax.numpy as jnp


class reference:
    @staticmethod
    def param_spec(model):
        return {"w/kernel": ((model["in_features"], model["out_features"]), 0.0, 0.5)}

    @staticmethod
    def state_spec(model):
        return {}

    @staticmethod
    def loss(model, job, params, batch, num):
        out = num.einsum("bi,io->bo", batch["x"], params["w"]["kernel"])
        return jnp.mean(jnp.square(out - batch["y"]))
'''
RECORDS = '''
import numpy as np

ROW_KEY = "x"


def make(spec, model, rng):
    return {"x": rng.standard_normal(model["in_features"], dtype=np.float32),
            "y": rng.standard_normal(model["out_features"], dtype=np.float32)}
'''


def with_new_family(tmp_path) -> Path:
    """A copy of the benchmark with a third family, a record kind, a
    configuration, a mix and a cell added as new files and entries only."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    b = shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "families/linear.py").write_text(FAMILY)
    (b / "families/linear_ref.py").write_text(REFERENCE)
    (b / "records/vectors.py").write_text(RECORDS)
    (b / "configs/linear.json").write_text(json.dumps({
        "family": "linear", "source": "x", "in_features": 8, "out_features": 4,
        "job": {"optimizer": "adafactor", "lr": 0.001}}))
    (b / "traffic/vectors.json").write_text(json.dumps({
        "shape": {"batch": 4}, "input": {"cache_in_memory": True},
        "records": {"kind": "vectors", "count": 32, "shards": 2}}))
    cell = json.loads((b / "workloads/mistral7b-s1024.json").read_text())
    cell.update(name="linear-tiny", rehearsal={})
    cell["loop"].update(warmup_steps=4, trace_seconds=0.5)
    (b / "workloads/linear-tiny.json").write_text(json.dumps(cell))
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "linear", "source": "x", "reduced": [],
                         "file": "benchmark/configs/linear.json", "why": "x"})
    m["workloads"].append({"name": "linear-tiny", "config": "linear",
                           "traffic": "vectors", "chips": 1, "why": "x"})
    for x in m["end_to_end"] + m["per_layer"]:
        if "mistral7b-s1024" in x.get("workloads", []):
            x["workloads"].append("linear-tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    assert {p: p.read_bytes() for p in before} == before
    return tmp_path


@pytest.mark.parametrize("cell,traced", [
    ("rn50-cached", 0), ("rn50-cached", 1), ("linear-tiny", 1),
    ("mistral7b-s8192", 1), ("mistral7b-s1024", 0)])
def test_rehearsal_end_to_end(cell, traced, tmp_path):
    where = {"cwd": with_new_family(tmp_path), "program": ROOT} \
        if cell not in CELLS else {}
    proc = run(["--workload", cell, "--seed", str(2 ** 31 + 12345),
                "--seconds", "1", "--trace", str(traced), "--rehearse"], **where)
    out = last_line(proc)
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(out)[-1] == "compared"
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    # no CPU number under a device metric's name
    assert not set(out["metrics"]) & DEVICE_METRICS
    assert "busy_s" not in out["device"] and "breakdown" not in out
    if traced:
        assert any(k.startswith("data_wait_share") for k in out["metrics"])
    for name, row in out["compared"].items():
        assert f"compared {name}: " in proc.stderr
    assert proc.stderr.strip().splitlines()[-1] == "correct: True"


def test_all_cells_are_rehearsed():
    assert set(CELLS) == {"rn50-cached", "mistral7b-s8192", "mistral7b-s1024"}


def test_without_a_tpu_it_fails_and_reports_nothing():
    proc = run(["--workload", "rn50-cached", "--seed", "1", "--seconds", "1",
                "--trace", "0"])
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_it_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark")
    proc = run(["--workload", "rn50-cached", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse"], cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.mark.parametrize("cell,fault", [
    ("rn50-cached", "state_unchanged"), ("rn50-cached", "half_batch"),
    ("mistral7b-s1024", "state_unchanged"), ("mistral7b-s8192", "half_batch")])
def test_a_broken_timed_path_reads_not_correct(cell, fault):
    """The harness as it is, the program's Trainer broken under it."""
    proc = run([fault, "--workload", cell, "--seed", "77", "--seconds", "0.5",
                "--trace", "0", "--rehearse"],
               script=(str(Path(__file__).parent / "fault_driver.py"),))
    out = last_line(proc)
    assert out["correct"] is False
    failed = [k for k, v in out["compared"].items()
              if v["limit"] is not None and v["value"] > v["limit"]]
    assert failed, out["compared"]
    if fault == "state_unchanged":
        assert out["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_the_window_waits_for_the_threads_the_cell_names():
    """The window opens after the warm-up pulls once no thread the cell's file
    names is alive, and closes at the first pull at or after its seconds."""
    import threading

    from benchmark.window import Window, WindowClosed

    class Rows:
        def batches(self, num_epochs=None):
            while True:
                yield {"x": 0}

    gate = threading.Event()
    busy = threading.Thread(target=gate.wait, name="compiles-behind", daemon=True)
    busy.start()
    w = Window(Rows(), seconds=0.05, warmup_pulls=2, keep_batches=1,
               background_threads=("compiles-behind",))
    it = w.batches()
    for _ in range(5):
        next(it)
    assert w.open_index is None          # past the warm-up, the thread alive
    gate.set()
    busy.join()
    with pytest.raises(WindowClosed):
        for _ in range(10 ** 6):
            next(it)
    assert w.open_index == 5 and w.closed and w.span_s() >= 0.05
    assert w.steps() == len(w.intervals_s()) and len(w.kept) == 1
