"""Every bundled example runs end-to-end (tiny configs, few steps) on the
8-fake-device CPU mesh in a subprocess — BASELINE configs 2-5.
(Config 1, CIFAR-10, has its own deeper test in test_example_cifar10.py.)
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(script, run_dir, *extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [
        sys.executable, str(REPO / "examples" / script),
        "--run-dir", str(run_dir),
        "--steps", "3", "--ckpt-every", "100", "--log-every", "1",
        *extra,
    ]
    return subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=600)


def _ok(r):
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "final: step=3" in r.stdout


def test_imagenet_resnet50_example(tmp_path):
    # resnet18 at 64px keeps the CPU run quick; same code path as resnet50
    _ok(_run("imagenet_resnet50.py", tmp_path, "--network", "resnet18",
             "--image-size", "64", "--batch-size", "16", "--num-examples", "64"))


def test_bert_base_example(tmp_path):
    _ok(_run("bert_base.py", tmp_path, "--tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64"))


def test_llama_fsdp_example(tmp_path):
    _ok(_run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64", "--fsdp", "2"))


def test_llama_ring_attention_example(tmp_path):
    _ok(_run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny", "--seq-len", "64",
             "--batch-size", "8", "--num-examples", "32", "--context", "4"))


def test_llama_pipeline_example(tmp_path):
    _ok(_run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64", "--pipeline", "2",
             "--microbatches", "2"))


def test_llama_pipeline_composed_example(tmp_path):
    """PP × TP × SP in one run (VERDICT r1 item 5: --pipeline no longer
    excludes --tensor/--context)."""
    r = _run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny", "--seq-len", "64",
             "--batch-size", "8", "--num-examples", "32", "--pipeline", "2",
             "--microbatches", "2", "--tensor", "2", "--context", "2")
    _ok(r)
    assert "bubble fraction" in r.stdout


def test_qwen3_next_moe_example(tmp_path):
    """The hybrid decoder through run_train_loop: the step's routing
    counters reach the log and one ``step_metrics`` trace line a step."""
    r = _run("qwen3_next_moe.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64")
    _ok(r)
    assert "moe_dropped=0 " in r.stdout and "moe_rows=" in r.stdout
    rows = [json.loads(ln) for p in (tmp_path / "trace").glob("trace-*.jsonl")
            for ln in p.read_text().splitlines()]
    lines = [x for x in rows if x["name"] == "step_metrics"]
    assert [x["trace_id"] for x in lines] == [1, 2, 3]
    assert all(x["attrs"]["moe_dropped"] == 0.0 and x["attrs"]["moe_rows"] > 0
               and x["attrs"]["moe_blocks_run"] >= 1.0 for x in lines)


def test_joyai_llm_flash_example(tmp_path):
    """The latent-attention decoder through run_train_loop: both losses and
    the routing counters reach the log and the ``step_metrics`` lines."""
    r = _run("joyai_llm_flash.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64")
    _ok(r)
    assert "moe_dropped=0 " in r.stdout and "mtp_loss=" in r.stdout
    rows = [json.loads(ln) for p in (tmp_path / "trace").glob("trace-*.jsonl")
            for ln in p.read_text().splitlines()]
    lines = [x for x in rows if x["name"] == "step_metrics"]
    assert [x["trace_id"] for x in lines] == [1, 2, 3]
    assert all(x["attrs"]["moe_dropped"] == 0.0 and x["attrs"]["mtp_loss"] > 0
               and x["attrs"]["lm_loss"] > 0
               and x["attrs"]["moe_blocks_run"] >= 1.0 for x in lines)


def test_granite4_h_example(tmp_path):
    """The state-space decoder through run_train_loop: the recurrence's two
    counters reach the log and the ``step_metrics`` lines."""
    r = _run("granite4_h.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64")
    _ok(r)
    assert "ssm_log_decay_min=-" in r.stdout and "ssm_state_rms=" in r.stdout
    rows = [json.loads(ln) for p in (tmp_path / "trace").glob("trace-*.jsonl")
            for ln in p.read_text().splitlines()]
    lines = [x for x in rows if x["name"] == "step_metrics"]
    assert [x["trace_id"] for x in lines] == [1, 2, 3]
    assert all(x["attrs"]["ssm_log_decay_min"] < 0 < x["attrs"]["ssm_state_rms"]
               for x in lines)


def test_olmo_hybrid_example(tmp_path):
    """The Olmo-Hybrid decoder through run_train_loop: the delta rule's two
    counters reach the log and the ``step_metrics`` lines."""
    r = _run("olmo_hybrid.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64")
    _ok(r)
    assert "gdn_reflect_share=" in r.stdout and "gdn_state_rms=" in r.stdout
    rows = [json.loads(ln) for p in (tmp_path / "trace").glob("trace-*.jsonl")
            for ln in p.read_text().splitlines()]
    lines = [x for x in rows if x["name"] == "step_metrics"]
    assert [x["trace_id"] for x in lines] == [1, 2, 3]
    assert all(0 < x["attrs"]["gdn_reflect_share"] < 1
               and x["attrs"]["gdn_state_rms"] > 0 for x in lines)


def test_dots3_note_example(tmp_path):
    """The sparse- and windowed-attention decoder through run_train_loop:
    the indexer's loss, the share of keys chosen and the routing counters
    reach the log and the ``step_metrics`` lines."""
    r = _run("dots3_note.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64")
    _ok(r)
    assert "dsa_kl=" in r.stdout and "moe_dropped=0 " in r.stdout
    rows = [json.loads(ln) for p in (tmp_path / "trace").glob("trace-*.jsonl")
            for ln in p.read_text().splitlines()]
    lines = [x for x in rows if x["name"] == "step_metrics"]
    assert [x["trace_id"] for x in lines] == [1, 2, 3]
    # 12 keys a query of up to 32: Σ min(12, t + 1) over Σ (t + 1)
    assert all(x["attrs"]["dsa_kl"] > 0 and x["attrs"]["dsa_select_share"]
               == pytest.approx((78 + 20 * 12) / 528) for x in lines)


def test_sd15_unet_example(tmp_path):
    _ok(_run("sd15_unet.py", tmp_path, "--tiny", "--batch-size", "8",
             "--num-examples", "32"))


@pytest.mark.parametrize("flag", ["--fsdp", "--tensor"])
def test_bert_parallel_modes(tmp_path, flag):
    _ok(_run("bert_base.py", tmp_path, "--tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64", flag, "2"))


def test_llama_pipeline_1f1b_example(tmp_path):
    r = _run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64", "--pipeline", "2",
             "--microbatches", "4", "--pp-schedule", "1f1b")
    _ok(r)


def test_llama_pipeline_interleaved_example(tmp_path):
    """Interleaved 1F1B through the example surface: P=2 x V=2 chunks."""
    r = _run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64", "--pipeline", "2",
             "--microbatches", "4", "--pp-schedule", "1f1b", "--pp-virtual", "2",
             "--layers", "4")
    _ok(r)

def test_llama_moe_1f1b_example(tmp_path):
    """MoE + expert axis + 1F1B: aux losses collected, accuracy logged."""
    r = _run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny", "--seq-len", "32",
             "--batch-size", "16", "--num-examples", "64", "--pipeline", "2",
             "--microbatches", "4", "--pp-schedule", "1f1b",
             "--moe-experts", "4", "--expert", "2")
    _ok(r)


def test_llama_moe_dense_path_example(tmp_path):
    """MoE on the non-PP path: sown aux collected via mutable apply."""
    _ok(_run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny", "--seq-len",
             "32", "--batch-size", "16", "--num-examples", "64",
             "--moe-experts", "4", "--expert", "2"))


def test_llama_lora_example(tmp_path):
    """--lora-rank trains adapters over a frozen FSDP-sharded base."""
    _ok(_run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny",
             "--seq-len", "32", "--batch-size", "8", "--fsdp", "2",
             "--lora-rank", "4"))


def test_llama_packed_example(tmp_path):
    """--packed: jsonl corpus -> packed shards -> segment-masked
    training with boundary-safe loss."""
    _ok(_run("llama3_8b_fsdp.py", tmp_path, "--model", "tiny",
             "--seq-len", "32", "--batch-size", "8", "--fsdp", "2",
             "--packed", "--num-examples", "64"))


@pytest.mark.slow
def test_anakin_rl_example(tmp_path):
    """Podracer RL loop through the example surface: actors + learner on
    the fake 8-device mesh, on-device replay, final line like the train
    examples."""
    _ok(_run("anakin_rl.py", tmp_path))


@pytest.mark.slow
def test_anakin_rl_gridworld_resume_example(tmp_path):
    """--stop-after interrupts, the rerun resumes from the snapshot and
    still lands on the same budget."""
    r0 = _run("anakin_rl.py", tmp_path, "--env", "gridworld",
              "--unroll", "8", "--ckpt-every", "2", "--stop-after", "2")
    assert r0.returncode == 0, f"stdout:\n{r0.stdout}\nstderr:\n{r0.stderr}"
    assert "final: step=2" in r0.stdout
    r = _run("anakin_rl.py", tmp_path, "--env", "gridworld", "--unroll", "8",
             "--ckpt-every", "2")
    _ok(r)
    assert "rl resumed from iteration 2" in r.stdout


def test_imagenet_multiprocess_loader_example(tmp_path):
    """--loader-workers -2: spawn decode workers feed the train loop."""
    _ok(_run("imagenet_resnet50.py", tmp_path, "--network", "resnet18",
             "--image-size", "64", "--batch-size", "8", "--augment",
             "--loader-workers", "-2", "--num-examples", "64"))


# ---- what only the process sees of run_train_loop ------------------------
# (tests/loop_probe_worker.py runs an example's main() and reports)

PROBED = {
    "resnet": ("imagenet_resnet50.py", [
        "--network", "resnet18", "--image-size", "32", "--batch-size", "8",
        "--num-examples", "32", "--num-classes", "10"]),
    "decoder": ("llama3_8b_fsdp.py", [
        "--model", "tiny", "--seq-len", "32", "--batch-size", "8",
        "--num-examples", "32"]),
}
_probes: dict = {}


def _probe(name, tmp_path_factory):
    if name not in _probes:
        script, extra = PROBED[name]
        d = tmp_path_factory.mktemp(f"probe-{name}")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
        # the heartbeat's thread is one of the loop's only under a coordinator
        env["TPUCFN_FT_DIR"] = str(d / "ft")
        env["TPUCFN_FT_HEARTBEAT_S"] = "0.2"
        r = subprocess.run(
            [sys.executable, str(REPO / "tests" / "loop_probe_worker.py"),
             str(REPO / "examples" / script), "--run-dir", str(d / "run"),
             "--steps", "4", "--ckpt-every", "100", "--log-every", "1", *extra],
            env=env, capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
        assert "final: step=4" in r.stdout
        _probes[name] = (json.loads(r.stdout.strip().splitlines()[-1]), d)
    return _probes[name]


@pytest.mark.parametrize("name", sorted(PROBED))
def test_the_loop_compiles_its_step_once(name, tmp_path_factory):
    """Four steps through run_train_loop lower and compile ``_step_fn`` once:
    no retrace at step 2 (a state whose avals or shardings moved), no second
    lowering from anywhere (a thread that wants the step's cost analysis)."""
    seen, _ = _probe(name, tmp_path_factory)
    msgs = seen["step_fn_messages"]
    assert sum(m.startswith("Compiling jit(_step_fn)") for m in msgs) == 1, msgs
    assert sum(m.startswith("Finished XLA compilation of jit(_step_fn)")
               for m in msgs) == 1, msgs


def test_the_loop_leaves_no_thread_behind(tmp_path_factory):
    """Once run_train_loop has returned, every thread it started has ended:
    the prefetcher (left in its put it holds three batches on the devices),
    the heartbeat, the obs endpoint, whatever a later PR starts.  (At these
    sizes no batch is large enough to start the process's assembly pool,
    which is the process's and stays.)"""
    seen, d = _probe("resnet", tmp_path_factory)
    assert list((d / "ft").glob("hb-host*.jsonl")), "no heartbeat was written"
    assert seen["threads_left"] == []
