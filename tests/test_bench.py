"""bench.py is one process on whatever device JAX gives it: it prints
exactly one parseable JSON row that names the device, measures the CPU
only when ``JAX_PLATFORMS=cpu`` asks for it by name, and fails otherwise
when there is no chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run_bench(extra_env=None, timeout=1200):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    # the CPU by name, at the tiny preset (conftest's XLA_FLAGS give it
    # 8 fake devices)
    env.update({"JAX_PLATFORMS": "cpu", "TPUCFN_BENCH_PRESET": "tiny"})
    for k, v in (extra_env or {}).items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable, str(REPO / "bench.py")],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_bench_emits_contract_json_line():
    r = _run_bench()
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    line = r.stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    for key in ("metric", "value", "unit", "vs_baseline", "detail"):
        assert key in rec, rec
    assert rec["value"] > 0
    d = rec["detail"]
    assert d["platform"] == "cpu" and d["device_kind"]
    assert "mean_step_s" in d and "time_to_first_step_s" in d
    # MFU machinery ran (flops measured; mfu itself is None off-TPU)
    assert d["flops_per_dev_step_g"] is not None
    assert d["mfu"] is None
    # ISSUE 18 first-class columns: warm TTFS, the served input leg, and
    # the goodput bucket decomposition ride every emitted row.
    assert isinstance(d["warm_time_to_first_step_s"], (int, float))
    assert d["warm_time_to_first_step_s"] > 0
    ov = d["overlap"]
    for k in ("loader_step_s", "served_step_s"):
        assert isinstance(ov[k], (int, float)) and ov[k] > 0, (k, ov)
    assert ov["served_source"] in ("in-process", "input-hosts"), ov
    gp = d["goodput"]
    assert gp["wall_s"] > 0
    assert 0.0 <= gp["goodput_ratio"] <= 1.0
    shares = gp["shares"]
    for k in ("step", "compile", "data_wait", "idle"):
        assert k in shares, shares
    assert all(0.0 <= v <= 1.0 for v in shares.values()), shares
    # the decomposition covers the wall: shares (idle filler included)
    # sum to 1 within rounding noise
    assert abs(sum(shares.values()) - 1.0) < 0.02, shares


def test_bench_llama_preset():
    r = _run_bench({"TPUCFN_BENCH_MODEL": "llama"})
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "tiny_llama_train_tokens_per_sec_per_chip"
    assert rec["unit"] == "tokens/sec/chip"
    assert rec["value"] > 0


def test_bench_without_a_chip_fails_and_prints_no_row():
    # No JAX_PLATFORMS: jax finds no TPU here and would fall back to the
    # CPU; a measurement path that finds no chip must fail instead.
    r = _run_bench({"JAX_PLATFORMS": None, "TPU_LOG_DIR": "disabled"},
                   timeout=300)
    assert r.returncode != 0, r.stdout[-2000:]
    assert r.stdout.strip() == "", r.stdout[-2000:]
    assert "no TPU" in r.stderr


def test_serve_bench_row_carries_prefix_and_batch_stats():
    """ISSUE 3 CI satellite: the serve_bench BENCH row must carry the
    shared-prefix block (hit rate, prefill calls per request, TTFT, the
    cache-off/on comparison) with sane values — a row missing them fails
    here instead of producing unreadable trajectory files.  Small run on
    CPU; the count-based numbers are deterministic."""
    import math

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, str(REPO / "benches" / "serve_bench.py"),
         "--requests", "24", "--max-new", "6", "--max-batch", "8",
         "--cache-len", "256", "--shared-prefix-len", "64",
         "--max-prefill-batch", "4"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "vs_baseline", "detail"):
        assert key in rec, rec
    assert rec["metric"] == "serve_tokens_per_sec"
    # ISSUE 5 acceptance: the BENCH row carries the serve_slo_* snapshot
    # (targets, objective, violation counts, rolling-window burn rates).
    slo = rec["detail"]["serve_slo"]
    for key in ("ttft_target_s", "tpot_target_s", "objective", "window_s",
                "requests", "window_requests", "ttft", "tpot"):
        assert key in slo, (key, slo)
    for objective in ("ttft", "tpot"):
        for key in ("violations_total", "window_violations", "burn_rate"):
            assert key in slo[objective], (objective, key)
    assert slo["requests"] == rec["detail"]["requests"]
    sp = rec["detail"]["shared_prefix"]
    for key in ("prefix_len", "requests", "max_prefill_batch",
                "prefill_calls_ceiling", "off", "on",
                "prefilled_tokens_reduction"):
        assert key in sp, sp
    for side in ("off", "on"):
        for key in ("prefill_calls", "prefill_calls_per_request",
                    "prefilled_tokens_per_request", "prefix_hit_rate",
                    "prefix_hit_tokens_per_request", "ttft_p50_s",
                    "ttft_p95_s", "kv_blocks_leaked"):
            assert key in sp[side], (side, key)
        assert sp[side]["kv_blocks_leaked"] == 0
    # The acceptance numbers themselves (token counts are deterministic).
    assert sp["off"]["prefix_hit_rate"] == 0.0
    assert sp["on"]["prefix_hit_rate"] > 0.5
    assert sp["prefilled_tokens_reduction"] >= 2.0
    assert sp["on"]["prefill_calls"] <= math.ceil(
        sp["requests"] / sp["max_prefill_batch"])
    assert sp["off"]["prefill_calls"] == sp["requests"]


def test_serve_bench_availability_row_schema():
    """ISSUE 9 CI satellite: `serve_bench --availability` emits the
    serve-side analogue of ft_bench's MTTR split — a BENCH row whose
    detail carries availability (accepted requests completing within
    deadline across a mid-trace replica kill), the retry success rate,
    and the hedge win rate.  Small run on CPU."""
    import pytest

    pytest.importorskip("jax")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, str(REPO / "benches" / "serve_bench.py"),
         "--availability", "--avail-requests", "12", "--max-new", "6",
         "--cache-len", "256", "--avail-deadline-s", "60"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "serve_availability"
    d = rec["detail"]
    for key in ("availability", "accepted", "rejected_at_submit",
                "dropped", "completed_ok", "retried",
                "retry_success_rate", "hedges", "hedge_win_rate",
                "failovers", "kill_at_request", "killed_at_s",
                "deadline_s", "interarrival_ms", "retry_budget",
                "hedge_ms", "seed", "router"):
        assert key in d, (key, sorted(d))
    assert rec["value"] == d["availability"]
    assert d["dropped"] == 0, "accepted requests must reach a terminal state"
    assert d["failovers"] == 1  # the scripted mid-trace kill
    # generous deadline on CPU: the kill must be absorbed, not paid for
    assert d["availability"] >= 0.99
    assert d["router"]["failed"] == 0


def test_data_bench_service_row_schema():
    """ISSUE 11 CI satellite: `data_bench --service` emits the
    disaggregated-input comparison row — local loader vs service-fed vs
    prestaged step time with stall shares — and gates rc on the
    served-within-1.5x-of-prestaged acceptance bound.  Tiny synthetic
    sleeps keep it fast; only the schema and the ordering invariants
    (loader stalls, served does not) are pinned, not absolute times."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, str(REPO / "benches" / "data_bench.py"),
         "--service", "--service-batches", "12", "--service-batch", "8",
         "--service-compute-ms", "30", "--service-decode-ms", "3",
         "--service-workers", "8"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["phase"] == "data_service"
    for key in ("loader_step_s", "served_step_s", "prestaged_step_s",
                "stall_share_local", "stall_share_served", "batch",
                "batches", "decode_s_per_example", "compute_s",
                "service_workers", "ok"):
        assert key in rec, (key, rec)
    # the local loader pays decode serially; the served path must not
    assert rec["stall_share_local"] > 0.2
    assert rec["stall_share_served"] < rec["stall_share_local"]
    assert rec["served_step_s"] < rec["loader_step_s"]
    assert rec["ok"] is True  # served within 1.5x of prestaged (rc gate)


def test_serve_bench_spec_row_schema():
    """ISSUE 14 CI satellite: `serve_bench --spec` emits the
    speculative-decoding BENCH row and rc-gates the two acceptance
    numbers — >= 1.5x tokens_per_target_step on the high-acceptance
    self-draft leg, worst-case TPOT within 1.3x of plain on the
    adversarial leg — with every leg's output bit-identical."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, str(REPO / "benches" / "serve_bench.py"),
         "--spec", "--cache-len", "192", "--prompt-len-hi", "64"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "serve_spec_tokens_per_target_step"
    d = rec["detail"]
    for leg in ("plain", "spec_high_acceptance", "spec_worst_case"):
        for key in ("tokens_per_target_step", "tpot_mean_s",
                    "decode_rounds", "wall_s"):
            assert key in d[leg], (leg, key)
    gates = d["gates"]
    assert gates["bit_identical"] is True
    assert gates["tokens_per_target_step_gate"] is True
    assert gates["worst_case_tpot_gate"] is True
    assert gates["tokens_per_target_step_gain"] >= 1.5
    assert gates["worst_case_tpot_ratio"] <= 1.3
    # The high-acceptance leg really speculated; the adversarial leg's
    # controller really reached its floor (off).
    assert d["spec_high_acceptance"]["acceptance_rate"] == 1.0
    assert d["spec_worst_case"]["acceptance_rate"] == 0.0
    assert d["spec_worst_case"]["controller_k_final"] == 0
    assert rec["value"] >= 1.5
