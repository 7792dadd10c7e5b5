"""The cell ``granite4h-ssd-s16384``: its files, the rehearsal end to end, the
control and the fault under the cell's own limits, the counts of operations and
bytes by hand, and the two new readers on a made-up trace.  The program
against the plain reference, leaf by leaf, is ``tests/test_ssm.py``'s."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import compare, flops, flops_granite4_h, readers, run
from benchmark.readers import trace
from benchmark.reference import granite4_h, train

sys.path.insert(0, str(Path(__file__).parent))
from test_bm_control import _batches  # noqa: E402
from test_bm_harness import last_line  # noqa: E402
from test_bm_harness import run as run_cell  # noqa: E402

CELL = "granite4h-ssd-s16384"
ROOT = Path(__file__).resolve().parents[2]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the catalog's ``config`` for granite-4.0-h-micro, key for key
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = {"num_hidden_layers": 10, "layer_types": PERIOD}


def test_the_configuration_keeps_every_catalog_key_and_cuts_no_width():
    cfg = run.load_cell(CELL, rehearse=False)["config"]
    for k, v in CATALOG.items():
        assert cfg["model"][k] == REDUCED.get(k, v), k
    assert cfg["published"] == {k: CATALOG[k] for k in REDUCED}
    assert CATALOG["layer_types"][:10] == PERIOD      # one whole period, 9 : 1
    for meta in ("source", "assumed", "deployment", "job"):
        assert cfg[meta], meta
    entry = next(c for c in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "configs"] if c["name"] == "granite-4.0-h-micro")
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == cfg["source"]
    model = cfg["model"]
    assert model["head_dim"] * 32 == 2048 and model["mamba_n_heads"] * 64 == 2 * 2048
    spec = granite4_h.param_spec(model)
    n = sum(int(np.prod(s)) for s, _, _ in spec.values())
    assert n == model["parameters_as_built"] == 951_991_232   # 7.6 GB at 8 bytes
    # a tied head: one table, no lm_head; the period's runs
    assert not any(p.startswith("lm_head") for p in spec)
    assert spec["embed_tokens/embedding"][0] == (100352, 2048)
    assert spec["periods/run0_mamba/mixer/in_proj/kernel"][0] == (1, 5, 2048, 8512)
    assert spec["periods/run1_attention/mixer/k_proj/kernel"][0] == (1, 2048, 512)
    assert spec["periods/run2_mamba/mixer/conv/kernel"][0] == (1, 4, 4, 4352)
    mix = run.load_cell(CELL, rehearse=False)["mix"]
    assert mix["shape"] == {"batch": 1, "seq_len": 16384}
    assert mix["records"] == {"kind": "tokens", "count": 256, "shards": 8,
                              "seq_len": 16384}


@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_end_to_end(traced):
    proc = run_cell(["--workload", CELL, "--seed", str(2 ** 31 + 4333),
                     "--seconds", "1", "--trace", str(traced), "--rehearse"])
    out = last_line(proc)
    assert list(out)[-1] == "compared" and out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["input_mismatch"] == {"value": 0, "limit": 0}
    assert out["compared"]["loss_gap"]["value"] < 1e-5   # float32 here: round-off
    if traced:   # the program's spans alone: no CPU number under a device metric
        assert set(out["metrics"]) == {"data_wait_share.g4h",
                                       "ssm_log_decay_min.g4h"}
        assert -100.0 < out["metrics"]["ssm_log_decay_min.g4h"]["value"] < 0.0
    else:
        assert out["metrics"] == {}


def test_at_rehearsal_size_the_control_and_the_fault_read_not_correct():
    """The mechanics only: at rehearsal size the program computes in float32
    and sits on the reference, so any rounding separates.  Whether the cell's
    limits hold the control off at the cell's own size is read on the chip
    (``read_limits.py``; PERF.md, Findings, PR 33)."""
    c = run.load_cell(CELL, rehearse=True)
    limits = run.load_cell(CELL, rehearse=False)["cell"]["check"]["limits"]
    seed = 2 ** 31 + 9
    batches = _batches(c, seed)
    ref = train.follow(c["config"], seed, batches)
    again = train.follow(c["config"], seed, batches)
    assert all(v == 0.0 for v in compare.numbers(again, ref).values())
    lim = {k: limits[k] for k in compare.NUMBERS if k in limits}
    for extra in ({"mode": "fp8"}, {"fault": "half_batch"}):
        values = compare.numbers(train.follow(c["config"], seed, batches, **extra), ref)
        ok, table = compare.verdict(values, lim)
        assert not ok, (extra, table)


def test_step_flops_by_hand():
    m = {**CATALOG, **REDUCED, "head_dim": 64}
    S, B = 16384, 1
    # in_proj to z (4096), x B C (4096 + 2 * 128) and dt (64); out_proj; 4 taps
    mixer = 2048 * (4096 + 4352 + 64) + 4096 * 2048 + 4 * 4352
    assert flops_granite4_h.mamba_layer_macs(m) == mixer == 25_838_592
    # a token at chunk 256: C.B against 256 positions of one group of 128; per
    # head of 64 the chunk-local sum over 256, the state's part of the output
    # and the token's part of the next state, each 64 x 128
    rec = 256 * 128 + 64 * 64 * (256 + 2 * 128)
    assert flops_granite4_h.recurrence_macs(m) == rec == 2_129_920
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert flops_granite4_h.attention_layer_macs(m) == attn == 10_485_760
    ffn = 3 * 2048 * 8192
    assert flops_granite4_h.ffn_macs(m) == ffn == 50_331_648
    per_token = 9 * (mixer + rec) + attn + 10 * ffn + 2048 * 100352
    scores = 2 * S * S * 64 * 32          # q k^T and p v, the half kept
    forward = B * (S * 2 * per_token + scores)
    assert flops_granite4_h.forward_flops(m, B, S) == pytest.approx(forward, rel=1e-12)
    assert forward / S == pytest.approx(2.009e9, rel=1e-3)       # a token, forward
    assert 10 * 2 * ffn * S / forward == pytest.approx(0.50, abs=0.005)
    assert 9 * 2 * (mixer + rec) * S / forward == pytest.approx(0.25, abs=0.005)
    assert 9 * 2 * rec * S / forward == pytest.approx(0.019, abs=0.001)
    assert 2 * 2048 * 100352 * S / forward == pytest.approx(0.205, abs=0.005)
    assert scores / forward == pytest.approx(0.033, abs=0.001)
    step = flops.train_step_flops({"family": "granite4_h", "model": m},
                                  {"batch": B, "seq_len": S})
    assert step == 3 * forward
    assert step == pytest.approx(98.76e12, rel=1e-3)      # 0.501 s at the chip's peak
    with pytest.raises(ValueError, match="layer_types"):
        flops_granite4_h.forward_flops({**m, "num_hidden_layers": 9}, B, S)


def test_an_ssd_call_counts_the_recurrence_whatever_implements_it():
    m = {**CATALOG, **REDUCED}
    S = 16384
    ops, moved = flops_granite4_h.ssd_call("fwd", 1, S, m)
    assert ops == 2 * 2_129_920 * S
    x = S * 64 * 64 * 2                   # and y: bfloat16
    bc = 2 * S * 128 * 2
    dt = S * 64 * 4
    states = 64 * 64 * 64 * 128 * 4       # a float32 state a chunk and head
    assert moved == x + bc + dt + x + states
    peak = flops.peaks("TPU v5 lite")
    assert flops.roofline_seconds(ops, moved, peak) == (
        pytest.approx(moved / 819e9, rel=1e-2), "memory")
    back, moved_back = flops_granite4_h.ssd_call("bwd", 1, S, m)
    assert back == 2 * ops and moved_back == 2 * (x + bc + dt) + x + states
    # the nine layers' one forward and one backward: 10.9 ms a step at least
    least = 9 * sum(flops.roofline_seconds(*flops_granite4_h.ssd_call(k, 1, S, m),
                                           peak)[0] for k in ("fwd", "bwd"))
    assert least == pytest.approx(0.0109, rel=0.02)


def _ctx(spans, devices=None, host_interval=(10.0, 20.0), cell=CELL):
    c = run.load_cell(cell, rehearse=False)
    return readers.Context(c["config"], c["mix"], 1, spans, host_interval, devices,
                           "_step_fn", flops.peaks("TPU v5 lite"))


def _metric(name):
    return json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())


# the head of an event's name as the chip writes it, by what it holds
SSD_EVENTS = [
    "%multiply_convert_fusion.9 = bf16[64,64,256,256]{3,2,1,0:T(8,128)(2,1)} fusion(",
    "%copy.31 = bf16[64,256,64,64]{3,2,1,0} copy(%param_0.6121)",
    "%fusion.77 = f32[64,256,256]{2,1,0} fusion(%bitcast.3391, %bitcast.3395)",
    "%convolution_bitcast_fusion.4 = f32[64,1,1,64,64,128]{5,4,3,2,1,0} fusion(",
    "%while.451 = (s32[]{:T(128)}, f32[1,1,64,64,128]{4,3,2,1,0:T(8,128)S(1)}, bf16[64,1,1",
]
OTHER_EVENTS = [
    "%fusion.9 = bf16[16384,8512]{1,0} fusion(%p)",
    "%while.3 = (s32[]{:T(128)}, f32[], f32[], bf16[32,1,512,2048]) while(",
    "%convolution_add_fusion.7 = f32[512,100352]{1,0} fusion(",
    "%fusion.12 = bf16[1,16384,32,64]{3,2,1,0} fusion(%q)",
]


def _made_up_trace(ssd_s, flash_s=None, with_ssd=True):
    """Three runs of the step's module, two of them inside the cut."""
    ops, modules = [], []
    for step in range(3):
        t = 100.0 + step
        modules.append(("jit__step_fn(123)", t, 0.9))
        names = [(n, 0.05) for n in OTHER_EVENTS]
        if with_ssd:
            names += [(n, ssd_s / len(SSD_EVENTS)) for n in SSD_EVENTS]
        if flash_s:
            names += [("%flash_fwd.1 = bf16[1,32,16384,64]", flash_s["fwd"]),
                      ("%flash_fwd.2 = bf16[1,32,16384,64]", flash_s["fwd"]),
                      ("%flash_dkv.3 = (bf16[1,8,16384,64]", flash_s["dkv"]),
                      ("%flash_dq.4 = bf16[1,32,16384,64]", flash_s["dq"])]
        for name, dur in names:
            ops.append((name, t, dur))
            t += dur
    return [trace.DeviceTrace("/device:TPU:0", ops, modules)]


def test_the_new_readers_on_a_made_up_trace():
    from benchmark.readers import (flash_roofline, kernel_time_share,
                                   span_attr_mean, ssd_roofline, ssd_time_share)

    peak = flops.peaks("TPU v5 lite")
    share, roof = _metric("ssd_time_share.g4h"), _metric("ssd_roofline.g4h")
    assert share["reader"] == "ssd_time_share" and roof["reader"] == "ssd_roofline"
    assert share["layer"] == roof["layer"] and share["workloads"] == [CELL]
    c = _ctx([], _made_up_trace(0.25))
    # 0.25 s of the recurrence in a step of 0.2 + 0.25 busy seconds
    assert ssd_time_share.read(c, **share.get("args", {})) == pytest.approx(
        100 * 0.25 / 0.45)
    least = 9 * sum(flops.roofline_seconds(*flops_granite4_h.ssd_call(
        k, 1, 16384, c.config["model"]), peak)[0] for k in ("fwd", "bwd"))
    assert ssd_roofline.read(c, **roof.get("args", {})) == pytest.approx(
        100 * least / 0.25)
    # a kernel of the program's own for it is found by its name
    named = _made_up_trace(0.25, with_ssd=False)
    named[0].ops.extend((f"%ssd_chunk_fwd.{i} = bf16[1,16384,4096]", 100.5 + i, 0.1)
                        for i in range(2))
    assert ssd_time_share.read(_ctx([], named)) == pytest.approx(100 * 0.1 / 0.3)
    # nothing to read: no trace, another family's configuration, a trace
    # without the recurrence (the parent has no such operation)
    assert ssd_time_share.read(_ctx([])) is None and ssd_roofline.read(_ctx([])) is None
    other = _ctx([], _made_up_trace(0.25), cell="qwen3next-ep8-s8192")
    assert ssd_time_share.read(other) is None and ssd_roofline.read(other) is None
    bare = _made_up_trace(0.25, with_ssd=False)
    assert ssd_time_share.read(_ctx([], bare)) is None
    assert ssd_roofline.read(_ctx([], bare)) is None
    # the flash kernels at 32 / 8 heads of 64 through the reader that was there
    m = _metric("flash_roofline.g4h")
    assert m["reader"] == "flash_roofline"
    one = {k: flops.roofline_seconds(*flops.flash_call(k, 1, 16384, 32, 8, 64),
                                     peak)[0] for k in ("fwd", "dkv", "dq")}
    assert one["fwd"] == pytest.approx(2 * 64 * 16384 * 16384 * 32 / 197e12, rel=1e-6)
    slow = _ctx([], _made_up_trace(0.1, {k: 2 * v for k, v in one.items()}))
    assert flash_roofline.read(slow, **m["args"]) == pytest.approx(50.0)
    whole = kernel_time_share.read(slow, **_metric("flash_time_share.g4h")["args"])
    parts = sum(kernel_time_share.read(slow, **_metric(f"flash_{k}_time_share.g4h")["args"])
                for k in ("fwd", "dkv", "dq"))
    assert 0 < whole < 100 and parts == pytest.approx(whole)
    # the counter, from the step's own line
    line = {"name": "step_metrics", "start": 15.0, "dur_s": 0.0,
            "attrs": {"ssm_log_decay_min": -412.5, "ssm_state_rms": 0.02}}
    d = _metric("ssm_log_decay_min.g4h")
    assert span_attr_mean.read(_ctx([line]), **d["args"]) == -412.5
    assert span_attr_mean.read(_ctx([]), **d["args"]) is None


def test_the_manifest_gives_the_cell_its_metrics():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "granite-4.0-h-micro", "traffic": "b1-s16384",
                    "chips": 1}
    mine = sorted(x["name"] for x in m["per_layer"] if CELL in x["workloads"])
    assert mine == sorted(n + ".g4h" for n in (
        "step_mfu", "device_idle_share", "data_wait_share", "idle_in_data_wait",
        "idle_in_step_wait", "idle_in_step_dispatch", "idle_in_loop",
        "flash_roofline", "flash_time_share", "flash_fwd_time_share",
        "flash_dkv_time_share", "flash_dq_time_share", "ssd_time_share",
        "ssd_roofline", "ssm_log_decay_min"))
    assert all(x["workloads"] == [CELL] for x in m["per_layer"]
               if x["name"].endswith(".g4h"))
    for x in m["per_layer"]:
        if x["name"].endswith(".g4h"):
            assert {k: _metric(x["name"])[k] for k in x} == x
    rate = next(x for x in m["end_to_end"] if x["name"] == "tokens_per_s_per_chip")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    check = run.load_cell(CELL, rehearse=False)["cell"]["check"]
    assert check["flash_kernel"] is True and check["limits"]["kernel_path_mismatch"] == 0
    assert set(check["limits_why"]) == set(compare.NUMBERS)
