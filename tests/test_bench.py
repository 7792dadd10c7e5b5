"""The tools under ``benches/`` that stay: each prints parseable rows with
its documented keys.  Counts and schemas are pinned here; a time or a ratio of
times read on a shared CPU is not (speed is ``benchmark/``'s, on the chip)."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# The builders' on-chip tools at the smallest sizes their arguments allow: a
# rename in ops/gated_delta.py, kernels/flash_attention.py or models/moe.py is
# found here and not on chip time.  (rows, keys every row carries, arguments)
KERNEL_TOOLS = {
    "gdn_bench.py": (
        4, ("path", "pass", "pallas", "median_ms", "batch", "seq",
            "key_heads", "value_heads", "head_dim", "chunk", "device"),
        ["--batch", "1", "--seq", "64", "--key-heads", "1", "--value-heads",
         "2", "--head-dim", "16", "--chunk", "16", "--iters", "1"]),
    "flash_bench.py": (
        1, ("s", "heads", "kv_heads", "d", "fwd_flash_ms", "fwd_dense_ms",
            "bwd_flash_ms", "bwd_dense_ms", "speedup_fwd", "speedup_bwd"),
        ["--seqs", "128", "--batch", "1", "--heads", "2", "--kv-heads", "1",
         "--head-dim", "32", "--iters", "1"]),
    "moe_bench.py": (
        6, ("preset", "load", "pass", "median_ms", "rows", "blocks_run",
            "dropped", "blocks", "block", "tokens", "dim", "held", "experts",
            "ffn_dim", "top_k", "device"),
        ["--preset", "joyai-mla-s8192", "--tokens", "64", "--dim", "32",
         "--ffn-dim", "16", "--iters", "1"]),
    "ssd_bench.py": (
        4, ("preset", "path", "pass", "pallas", "median_ms", "batch", "seq",
            "heads", "head_dim", "state", "groups", "chunk", "device"),
        ["--seq", "64", "--heads", "4", "--head-dim", "8", "--state", "16",
         "--chunk", "16", "--iters", "1"]),
}


@pytest.mark.parametrize("tool", sorted(KERNEL_TOOLS))
def test_kernel_tool_prints_its_documented_rows(tool):
    n_rows, keys, argv = KERNEL_TOOLS[tool]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(REPO / "benches" / tool), *argv],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    assert len(rows) == n_rows, rows
    for row in rows:
        assert set(keys) <= set(row), (sorted(row), keys)
        for k, v in row.items():
            if k.endswith("_ms"):
                assert isinstance(v, float) and math.isfinite(v) and v > 0, row
    if tool == "gdn_bench.py":
        assert [(row["path"], row["pass"]) for row in rows] == [
            ("jnp", "fwd"), ("jnp", "fwd_bwd"),
            ("kernel", "fwd"), ("kernel", "fwd_bwd")]
        # off a TPU both paths are the jnp preparation, and the row says so
        assert not any(row["pallas"] for row in rows)
        assert {row["device"] for row in rows} == {"cpu"}
    if tool == "ssd_bench.py":
        assert [(row["path"], row["pass"]) for row in rows] == [
            ("jnp", "fwd"), ("jnp", "fwd_bwd"),
            ("model", "fwd"), ("model", "fwd_bwd")]
        # off a TPU (and at a head of 8) the model takes the jnp form too
        assert not any(row["pallas"] for row in rows)
    if tool == "moe_bench.py":
        # 64 tokens x 8 in blocks of 64: half a block, one and a half, all 8
        assert [(row["load"], row["pass"], row["rows"], row["blocks_run"])
                for row in rows] == [
            (load, name, n, run) for load, n, run in (
                ("one_block", 32, 1), ("two_blocks", 96, 2), ("all_blocks", 512, 8))
            for name in ("fwd", "fwd_bwd")]
        assert {(row["blocks"], row["block"], row["dropped"]) for row in rows} == {
            (8, 64, 0)}


def test_flash_bench_presets_are_the_flash_cells_shapes():
    """``--preset`` names a benchmark cell and times the kernels at its
    shape: the shapes are the cell's configuration and traffic files'."""
    sys.path.insert(0, str(REPO))
    from benches import flash_bench

    from benchmark import run

    for name, shape in flash_bench.PRESETS.items():
        cell = run.load_cell(name, False)
        model, mix = cell["config"]["model"], cell["mix"]["shape"]
        want = dict(
            batch=mix["batch"], seq=mix["seq_len"],
            heads=model["num_attention_heads"],
            kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"])
        if "qk_head_dim" in model:   # latent attention: keys and values differ
            want.update(head_dim=model["qk_head_dim"],
                        value_dim=model["v_head_dim"])
        assert shape == want, name


def test_ssd_bench_preset_is_the_state_space_cells_shape():
    """The preset is the cell's configuration and traffic files', and the
    least time beside it is the cell's own yardstick's."""
    sys.path.insert(0, str(REPO))
    from benches import ssd_bench

    from benchmark import flops, flops_granite4_h, run

    (name, shape), = ssd_bench.PRESETS.items()
    cell = run.load_cell(name, False)
    model, mix = cell["config"]["model"], cell["mix"]["shape"]
    assert shape == dict(
        batch=mix["batch"], seq=mix["seq_len"], heads=model["mamba_n_heads"],
        head_dim=model["mamba_d_head"], state=model["mamba_d_state"],
        groups=model["mamba_n_groups"], chunk=model["mamba_chunk_size"])
    peak = flops.peaks("TPU v5 lite")
    least, bound = ssd_bench.least_ms(shape, ("fwd", "bwd"), peak)
    want = sum(flops.roofline_seconds(*flops_granite4_h.ssd_call(
        k, 1, 16384, model), peak)[0] for k in ("fwd", "bwd"))
    assert least == pytest.approx(1e3 * want) and bound == "compute"


def test_moe_bench_presets_are_the_sparse_cells_shapes():
    """``--preset`` names a benchmark cell and times the expert layer at its
    shape: the shapes are the cell's configuration and traffic files'."""
    sys.path.insert(0, str(REPO))
    from benches import moe_bench

    from benchmark import run

    held = {"joyai-mla-s8192": "n_routed_experts",
            "qwen3next-ep8-s8192": "num_experts"}
    for name, shape in moe_bench.PRESETS.items():
        cell = run.load_cell(name, False)
        model, mix = cell["config"]["model"], cell["mix"]["shape"]
        assert shape["tokens"] == mix["batch"] * mix["seq_len"], name
        assert (shape["dim"], shape["ffn_dim"], shape["top_k"]) == (
            model["hidden_size"], model["moe_intermediate_size"],
            model["num_experts_per_tok"]), name
        assert (shape["held"], shape["experts"]) == (
            model[held[name]], model["router_experts"]), name


def test_flash_bench_times_each_kernel_beside_its_roofline():
    """The preset mode's row at a size the interpreter can run: a time for
    each of the three kernels, the least time from ``benchmark/flops``
    (imported, the cells' yardstick), and the grid steps by class."""
    sys.path.insert(0, str(REPO))
    from benches import flash_bench
    from benchmark import flops

    shape = dict(batch=1, seq=256, heads=2, kv_heads=1, head_dim=32)
    times = flash_bench.kernel_times(**shape, blocks=(64, 128), iters=1)
    assert times["blocks"] == [64, 128]
    # 4 query blocks x 2 key blocks: (0,0) (1,0) are cut by the diagonal's
    # first key block, (2,1) (3,1) by its second; (2,0) (3,0) lie under it
    assert times["steps"] == {"interior": 2, "edge": 4, "skipped": 2}
    peak = json.loads((REPO / "benchmark" / "peaks.json").read_text())[
        "TPU v5 lite"]
    row = flash_bench.beside_roofline(shape, times, peak)
    for kind in ("fwd", "dkv", "dq"):
        ms = row[kind]["ms"]
        assert isinstance(ms, float) and math.isfinite(ms) and ms > 0
        least, bound = flops.roofline_seconds(
            *flops.flash_call(kind, 1, 256, 2, 1, 32), peak)
        assert row[kind]["least_ms"] == round(least * 1e3, 3)
        assert row[kind]["bound"] == bound
    assert row["steps"] == times["steps"]


def test_serve_bench_row_carries_prefix_and_batch_stats():
    """ISSUE 3 CI satellite: the serve_bench BENCH row must carry the
    shared-prefix block (hit rate, prefill calls per request, TTFT, the
    cache-off/on comparison) with sane values — a row missing them fails
    here instead of producing unreadable trajectory files.  Small run on
    CPU; the count-based numbers are deterministic."""
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
    speculative-decoding BENCH row with every leg's output bit-identical
    and >= 1.5x tokens_per_target_step on the high-acceptance self-draft
    leg: counts, the same on any machine.  The script also gates its exit
    code on worst-case TPOT within 1.3x of plain, a ratio of two wall times:
    that gate is for a quiet machine, so here the number must be there and
    finite, and an exit code of 1 is allowed when that gate alone is false."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, str(REPO / "benches" / "serve_bench.py"),
         "--spec", "--cache-len", "192", "--prompt-len-hi", "64"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode in (0, 1), f"stderr:\n{r.stderr[-2000:]}"
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
    assert gates["tokens_per_target_step_gain"] >= 1.5
    ratio = gates["worst_case_tpot_ratio"]
    assert isinstance(ratio, float) and math.isfinite(ratio) and ratio > 0
    # the exit code says what the gates say, the timing gate among them
    assert (r.returncode == 0) == gates["worst_case_tpot_gate"], r.stderr[-2000:]
    # The high-acceptance leg really speculated; the adversarial leg's
    # controller really reached its floor (off).
    assert d["spec_high_acceptance"]["acceptance_rate"] == 1.0
    assert d["spec_worst_case"]["acceptance_rate"] == 0.0
    assert d["spec_worst_case"]["controller_k_final"] == 0
    assert rec["value"] >= 1.5
