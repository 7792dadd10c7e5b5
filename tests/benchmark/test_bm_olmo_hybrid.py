"""The cell ``olmohybrid-gdn-s16384``: its files, the rehearsal end to end, the
control and the fault under the cell's own limits, the counts of operations and
bytes by hand, and the new reader and metrics on a made-up trace.  The program
against the plain reference, leaf by leaf, is ``tests/test_olmo_hybrid.py``'s."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import compare, flops, flops_olmo_hybrid, readers, run
from benchmark.readers import trace
from benchmark.reference import olmo_hybrid, train

sys.path.insert(0, str(Path(__file__).parent))
from test_bm_control import _batches  # noqa: E402
from test_bm_harness import last_line  # noqa: E402
from test_bm_harness import run as run_cell  # noqa: E402

CELL = "olmohybrid-gdn-s16384"
ROOT = Path(__file__).resolve().parents[2]
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the catalog's ``config`` for Olmo-Hybrid-7B, key for key
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": PERIOD * 8, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
REDUCED = {"num_hidden_layers": 4, "layer_types": PERIOD, "vocab_size": 25088}


def test_the_configuration_keeps_every_catalog_key_and_cuts_no_width():
    c = run.load_cell(CELL, rehearse=False)
    cfg = c["config"]
    for k, v in CATALOG.items():
        assert cfg["model"][k] == REDUCED.get(k, v), k
    assert cfg["published"] == {k: CATALOG[k] for k in REDUCED}
    for meta in ("source", "assumed", "deployment", "job"):
        assert cfg[meta], meta
    entry = next(x for x in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "configs"] if x["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    model = cfg["model"]
    assert model["head_dim"] * 30 == 3840
    assert olmo_hybrid.layout(model) == (1, 3)        # one whole period, 3 : 1
    spec = olmo_hybrid.param_spec(model)
    n = sum(int(np.prod(s)) for s, _, _ in spec.values())
    assert n == model["parameters_as_built"] == 1_025_200_116   # 8.2 GB at 8 bytes
    assert spec["embed_tokens/embedding"][0] == (25088, 3840)
    assert spec["lm_head/kernel"][0] == (3840, 25088)
    assert spec["periods/linear/mixer/k_proj/kernel"][0] == (1, 3, 3840, 2880)
    assert spec["periods/linear/mixer/v_proj/kernel"][0] == (1, 3, 3840, 5760)
    assert spec["periods/linear/mixer/conv/kernel"][0] == (1, 3, 4, 11520)
    assert spec["periods/full/mixer/q_norm/scale"][0] == (1, 3840)
    assert c["mix"]["shape"] == {"batch": 1, "seq_len": 16384}
    job = cfg["job"]
    assert (job["remat"], job["ce_chunk"], job["optimizer"]) == ("full", 512, "adafactor")


@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_end_to_end(traced):
    proc = run_cell(["--workload", CELL, "--seed", str(2 ** 31 + 4383),
                     "--seconds", "1", "--trace", str(traced), "--rehearse"])
    out = last_line(proc)
    assert list(out)[-1] == "compared" and out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["input_mismatch"] == {"value": 0, "limit": 0}
    if traced:   # the program's spans alone: no CPU number under a device metric
        assert set(out["metrics"]) == {"data_wait_share.olh", "gdn_reflect_share.olh"}
        assert 0.0 < out["metrics"]["gdn_reflect_share.olh"]["value"] < 1.0
    else:
        assert out["metrics"] == {}


def test_at_rehearsal_size_the_control_and_the_fault_read_not_correct():
    """The mechanics only: at rehearsal size the program computes in float32
    and sits on the reference, so any rounding separates.  Whether the cell's
    limits hold the control off at the cell's own size is read on the chip
    (``read_limits.py``; PERF.md, Findings, PR 38)."""
    c = run.load_cell(CELL, rehearse=True)
    limits = run.load_cell(CELL, rehearse=False)["cell"]["check"]["limits"]
    seed = 2 ** 31 + 38
    batches = _batches(c, seed)
    ref = train.follow(c["config"], seed, batches)
    again = train.follow(c["config"], seed, batches)
    assert all(v == 0.0 for v in compare.numbers(again, ref).values())
    lim = {k: limits[k] for k in compare.NUMBERS if k in limits}
    for extra in ({"mode": "fp8"}, {"fault": "half_batch"}):
        values = compare.numbers(train.follow(c["config"], seed, batches, **extra), ref)
        ok, table = compare.verdict(values, lim)
        assert not ok, (extra, table)


def _model():
    return {**CATALOG, **REDUCED, "head_dim": 128}


def test_step_flops_by_hand():
    m = _model()
    S, B = 16384, 1
    # q, k (2,880), v, z (5,760), b and a (30 each) in; out_proj; 4 taps
    mixer = 3840 * (2 * 2880 + 2 * 5760 + 2 * 30) + 5760 * 3840 + 4 * (2 * 2880 + 5760)
    rule = 3 * 30 * 96 * 192               # S^T k, k u^T, S^T q a value head
    attn = 4 * 3840 * 3840                 # q, k, v, o: 30 heads of 128
    ffn = 3 * 3840 * 11008
    head = 3840 * 25088
    assert flops_olmo_hybrid.attention_layer_macs(m) == attn
    assert flops_olmo_hybrid.ffn_macs(m) == ffn == 126_812_160
    per_token = 3 * (mixer + rule) + attn + 4 * ffn + head
    scores = 2 * S * S * 128 * 30          # q k^T and p v, the half kept
    forward = B * (S * 2 * per_token + scores)
    assert flops_olmo_hybrid.forward_flops(m, B, S) == pytest.approx(forward, rel=1e-12)
    assert forward / S == pytest.approx(1.993e9, rel=1e-3)       # a token, forward
    shares = {"delta projections": 3 * 2 * mixer * S, "swiglu": 4 * 2 * ffn * S,
              "head": 2 * head * S, "attention": 2 * attn * S + scores,
              "rule": 3 * 2 * rule * S}
    assert shares["delta projections"] / forward == pytest.approx(0.267, abs=0.005)
    assert shares["swiglu"] / forward == pytest.approx(0.509, abs=0.005)
    assert shares["head"] / forward == pytest.approx(0.097, abs=0.005)
    assert shares["attention"] / forward == pytest.approx(0.122, abs=0.005)
    assert shares["rule"] / forward < 0.01
    step = flops.train_step_flops({"family": "olmo_hybrid", "model": m},
                                  {"batch": B, "seq_len": S})
    assert step == 3 * forward
    assert step == pytest.approx(97.98e12, rel=1e-3)      # 0.497 s at the chip's peak


def test_a_gdn_call_counts_the_rule_at_the_models_own_head_sizes():
    m = _model()
    S = 16384
    ops, moved = flops_olmo_hybrid.gdn_call("fwd", 1, S, m, 64)
    assert ops == 2 * 3 * 30 * 96 * 192 * S
    qk = 2 * S * 30 * 96 * 2               # q and k, bfloat16, 96 wide: not 128
    v = S * 30 * 192 * 2                   # and o: 192 wide, not 256
    gb = 2 * S * 30 * 4
    states = 256 * 30 * 96 * 192 * 4      # a float32 state a chunk of 64 and head
    assert moved == qk + v + gb + v + states
    peak = flops.peaks("TPU v5 lite")
    assert flops.roofline_seconds(ops, moved, peak) == (
        pytest.approx(moved / 819e9, rel=1e-2), "memory")
    back, moved_back = flops_olmo_hybrid.gdn_call("bwd", 1, S, m, 64)
    assert back == 2 * ops and moved_back == 2 * (qk + v + gb) + v + states
    # the three layers' one forward and one backward: 9.7 ms a step at least
    least = 3 * sum(flops.roofline_seconds(*flops_olmo_hybrid.gdn_call(
        k, 1, S, m, 64), peak)[0] for k in ("fwd", "bwd"))
    assert least == pytest.approx(0.00972, rel=0.02)


def _ctx(spans, devices=None, host_interval=(10.0, 20.0), cell=CELL):
    c = run.load_cell(cell, rehearse=False)
    return readers.Context(c["config"], c["mix"], 1, spans, host_interval, devices,
                           "_step_fn", flops.peaks("TPU v5 lite"))


def _metric(name):
    return json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())


# the head of an event's name as the chip writes it, by what it holds: the
# kernels' padded chunk tensors, a row of chunk scalars, the scan over chunks
# whose carry is the state at the heads' own sizes
GDN_EVENTS = [
    "%gdn_prep_fwd.3 = (bf16[256,1,30,1,64,128]{5,4,3,2,1,0}, bf16[256,1,30,1,64,256]",
    "%gdn_prep_bwd.1 = (f32[1,30,1,256,64]{4,3,2,1,0}, f32[1,30,1,256,64]",
    "%fusion.41 = f32[1,30,1,256,64]{4,3,2,1,0} fusion(%param.3)",
]
SCAN_EVENT = ("%while.12 = (s32[]{:T(128)}, f32[1,30,1,96,192]{4,3,2,1,0:T(8,128)}, "
              "bf16[256,1,30,1,64,128]")
OTHER_EVENTS = [
    "%fusion.9 = bf16[16384,11008]{1,0} fusion(%p)",
    "%while.3 = (s32[]{:T(128)}, f32[], f32[], bf16[32,1,512,3840]) while(",
    "%convolution_add_fusion.7 = f32[512,25088]{1,0} fusion(",
    "%fusion.12 = bf16[1,16384,30,128]{3,2,1,0} fusion(%q)",
]


def _made_up_trace(prep_s, scan_s, flash_s=None):
    """Three runs of the step's module, two of them inside the cut."""
    ops, modules = [], []
    for step in range(3):
        t = 100.0 + step
        modules.append(("jit__step_fn(123)", t, 0.9))
        names = [(n, 0.05) for n in OTHER_EVENTS]
        names += [(n, prep_s / len(GDN_EVENTS)) for n in GDN_EVENTS if prep_s]
        if scan_s:
            names.append((SCAN_EVENT, scan_s))
        if flash_s:
            names += [("%flash_fwd.1 = bf16[1,30,16384,128]", flash_s["fwd"]),
                      ("%flash_dkv.3 = (bf16[1,30,16384,128]", flash_s["dkv"]),
                      ("%flash_dq.4 = bf16[1,30,16384,128]", flash_s["dq"])]
        for name, dur in names:
            ops.append((name, t, dur))
            t += dur
    return [trace.DeviceTrace("/device:TPU:0", ops, modules)]


def test_the_new_reader_and_metrics_on_a_made_up_trace():
    from benchmark.readers import (flash_roofline, gdn_roofline, gdn_time_share,
                                   kernel_time_share, span_attr_mean)

    peak = flops.peaks("TPU v5 lite")
    share, scan = _metric("gdn_time_share.olh"), _metric("gdn_scan_time_share.olh")
    roof = _metric("gdn_roofline.olh")
    assert (share["reader"], scan["reader"], roof["reader"]) == (
        "gdn_time_share", "kernel_time_share", "gdn_roofline")
    assert share["layer"] == scan["layer"] == roof["layer"] == "delta rule (ops/gated_delta)"
    c = _ctx([], _made_up_trace(0.15, 0.05))
    # 0.2 s of the rule in a step of 0.2 + 0.2 busy seconds, the scan 0.05
    assert gdn_time_share.read(c, **share["args"]) == pytest.approx(100 * 0.2 / 0.4)
    assert kernel_time_share.read(c, **scan["args"]) == pytest.approx(100 * 0.05 / 0.4)
    least = 3 * sum(flops.roofline_seconds(*flops_olmo_hybrid.gdn_call(
        k, 1, 16384, c.config["model"], 64), peak)[0] for k in ("fwd", "bwd"))
    assert gdn_roofline.read(c, **roof["args"]) == pytest.approx(100 * least / 0.2)
    # nothing to read: no trace; a trace without the rule (the parent has no
    # such program); another family's configuration
    assert gdn_roofline.read(_ctx([]), **roof["args"]) is None
    bare = _ctx([], _made_up_trace(0.0, 0.0))
    assert gdn_roofline.read(bare, **roof["args"]) is None
    assert gdn_time_share.read(bare, **share["args"]) is None
    other = _ctx([], _made_up_trace(0.15, 0.05), cell="qwen3next-ep8-s8192")
    assert gdn_roofline.read(other, **roof["args"]) is None
    # the flash kernels at 30 / 30 heads of 128 through the reader that was there
    m = _metric("flash_roofline.olh")
    assert m["reader"] == "flash_roofline"
    one = {k: flops.roofline_seconds(*flops.flash_call(k, 1, 16384, 30, 30, 128),
                                     peak)[0] for k in ("fwd", "dkv", "dq")}
    slow = _ctx([], _made_up_trace(0.1, 0.0, {k: 2 * v for k, v in one.items()}))
    assert flash_roofline.read(slow, **m["args"]) == pytest.approx(50.0)
    whole = kernel_time_share.read(slow, **_metric("flash_time_share.olh")["args"])
    parts = sum(kernel_time_share.read(slow, **_metric(f"flash_{k}_time_share.olh")["args"])
                for k in ("fwd", "dkv", "dq"))
    assert 0 < whole < 100 and parts == pytest.approx(whole)
    # the counter, from the step's own line
    line = {"name": "step_metrics", "start": 15.0, "dur_s": 0.0,
            "attrs": {"gdn_reflect_share": 0.49, "gdn_state_rms": 0.02}}
    d = _metric("gdn_reflect_share.olh")
    assert span_attr_mean.read(_ctx([line]), **d["args"]) == 0.49
    assert span_attr_mean.read(_ctx([]), **d["args"]) is None


def test_the_manifest_gives_the_cell_its_metrics():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "olmo-hybrid-7b", "traffic": "b1-s16384",
                    "chips": 1}
    mine = sorted(x["name"] for x in m["per_layer"] if CELL in x["workloads"])
    assert mine == sorted(n + ".olh" for n in (
        "step_mfu", "device_idle_share", "data_wait_share", "idle_in_data_wait",
        "idle_in_step_wait", "idle_in_step_dispatch", "idle_in_loop",
        "flash_roofline", "flash_time_share", "flash_fwd_time_share",
        "flash_dkv_time_share", "flash_dq_time_share", "gdn_time_share",
        "gdn_scan_time_share", "gdn_roofline", "gdn_reflect_share"))
    for x in m["per_layer"]:
        if x["name"].endswith(".olh"):
            assert x["workloads"] == [CELL]
            assert {k: _metric(x["name"])[k] for k in x} == x
    rate = next(x for x in m["end_to_end"] if x["name"] == "tokens_per_s_per_chip")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    check = run.load_cell(CELL, rehearse=False)["cell"]["check"]
    assert check["flash_kernel"] is True and check["limits"]["kernel_path_mismatch"] == 0
    assert set(check["limits_why"]) == set(compare.NUMBERS)
