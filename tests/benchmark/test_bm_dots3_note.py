"""The cell ``dots3-dsa-s16384``: its files, the rehearsal end to end through
its driver (the program's choice handed to the reference), the counts of
operations and bytes by hand, and the new reader and metrics on a made-up
trace.  The program against the plain reference, leaf by leaf, is
``tests/test_dots3_note.py``'s; whether the fp8 controls fail the cell's
limits is read on the chip at the cell's own size (``read_limits.py``, and
for the choice ``read_choice.py``), where the program computes in
bfloat16."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import compare, flops, flops_dots3_note, readers, run
from benchmark.readers import trace
from benchmark.reference import dots3_note


CELL = "dots3-dsa-s16384"
ROOT = Path(__file__).resolve().parents[2]
FULL, SWA = "full_attention", "sliding_attention"
# the catalog's ``config`` for dots3-note-prev, key for key
CATALOG = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512,
    "layer_types": [FULL, FULL] + [SWA, SWA, SWA, FULL] * 11,
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128, "tie_word_embeddings": False,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152064}
REDUCED = {"num_hidden_layers": 5, "layer_types": [FULL, FULL, SWA, SWA, SWA],
           "n_routed_experts": 8, "num_attention_heads": 32,
           "num_key_value_heads": 32, "swa_num_attention_heads": 16,
           "swa_num_key_value_heads": 16, "vocab_size": 19008}


def test_the_configuration_keeps_every_catalog_key_and_cuts_no_width():
    c = run.load_cell(CELL, rehearse=False)
    cfg = c["config"]
    assert len(CATALOG["layer_types"]) == 46
    for k, v in CATALOG.items():
        assert cfg["model"][k] == REDUCED.get(k, v), k
    for k in REDUCED:
        assert k in cfg["published"], k
    for meta in ("source", "assumed", "deployment", "job"):
        assert cfg[meta], meta
    assumed = " ".join(cfg["assumed"])
    for reading in ("apply_mla_qkv_lora_rescale", "0 <= t - s < 513", "headwise",
                    "LayerNorm", "index_kl_weight 1", "multi-token prediction",
                    "vision or audio tower", "8 groups"):
        assert reading in assumed, reading
    entry = next(x for x in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "configs"] if x["name"] == "dots3-note-prev")
    assert entry["reduced"] == list(REDUCED) == cfg["model"]["reduced"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json")
    model = cfg["model"]
    assert dots3_note.layout(model) == (1, 3)         # one whole period, 1 : 3
    spec = dots3_note.param_spec(model)
    n = sum(int(np.prod(s)) for s, _, _ in spec.values())
    assert n == model["parameters_as_built"] == 1_452_459_520  # 11.6 GB at 8 bytes
    assert spec["embed_tokens/embedding"][0] == (19008, 5120)
    assert spec["dense_0/mixer/q_b_proj/kernel"][0] == (1024, 32 * 192)
    assert spec["layers/full/indexer/wq_b/kernel"][0] == (1, 1024, 64 * 128)
    assert spec["layers/window_2/mixer/kv_b_proj/kernel"][0] == (1, 1024, 16 * 320)
    assert spec["layers/window_0/mixer/gate_proj/kernel"][0] == (1, 5120, 16)
    assert spec["layers/full/mlp/experts/up_proj/kernel"][0] == (1, 8, 5120, 1536)
    assert c["mix"]["shape"] == {"batch": 1, "seq_len": 16384}
    job = cfg["job"]
    assert (job["remat"], job["ce_chunk"], job["optimizer"]) == ("full", 512, "adafactor")


def test_rehearsal_end_to_end_with_the_programs_choice(monkeypatch):
    """The traced run through the cell's driver, in this process: the
    reference attends over the keys the program chose, the two choices are
    compared before every step compared and agree where nothing rounds, and
    the fp8 control's own choice reads not correct under the margin."""
    import argparse

    from benchmark.drivers import train_dsa

    differ = dots3_note.selection_check
    checked = []

    def counted(*a, **kw):
        check = differ(*a, **kw)

        def one(*b):
            checked.append(check(*b))
            return checked[-1]
        return one

    monkeypatch.setattr(dots3_note, "selection_check", counted)
    c = run.load_cell(CELL, rehearse=True)
    keep: dict = {}
    seed = 2 ** 31 + 4413
    out = train_dsa.run(c, argparse.Namespace(
        workload=CELL, seed=seed, seconds=1.0, trace=1, rehearse=True),
        run.find_devices(1, rehearse=True), keep)
    assert list(out)[-1] == "compared" and out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["select_mismatch"] == {"value": 0, "limit": 0}
    assert out["compared"]["select_gap"] == {"value": 0.0, "limit": None}
    steps = c["cell"]["check"]["steps"]
    assert checked == [(0, 0.0)] * steps
    assert [sorted(b) for b in keep["batches"]] == [["dsa_words", "tokens"]] * steps
    # the program's spans alone: no CPU number under a device metric
    assert set(out["metrics"]) == {"data_wait_share.d3n", "dsa_kl.d3n",
                                   "moe_load_max_over_mean.d3n"}
    assert out["metrics"]["dsa_kl.d3n"]["value"] > 0
    control = train_dsa.select_control(c, seed, keep["batches"])
    assert control["select_mismatch"] > 0
    assert control["select_gap"] > c["cell"]["check"]["select_margin"]


def test_the_driver_fails_where_the_choice_was_not_compared(monkeypatch):
    """Where ``train.run`` no longer goes through the tap and the reference
    the driver replaces, the run fails: the choice never drops out of the
    verdict unseen."""
    import argparse

    from benchmark.drivers import train, train_dsa

    monkeypatch.setattr(train, "run", lambda c, a, devices, keep=None: {
        "correct": True, "compared": {}})
    with pytest.raises(RuntimeError, match="the choice was not compared"):
        train_dsa.run(run.load_cell(CELL, rehearse=True), argparse.Namespace(
            workload=CELL, seed=1, seconds=1.0, trace=0, rehearse=True), [])


def _model():
    return run.load_cell(CELL, rehearse=False)["config"]["model"]


def test_step_flops_by_hand():
    m = _model()
    S, B, d = 16384, 1, 5120
    full = (d * 1024 + 1024 * 32 * 192 + d * (512 + 64) + 512 * 32 * 256
            + d * 32 + 32 * 128 * d)
    window = (d * 1024 + 1024 * 16 * 256 + d * (1024 + 64) + 1024 * 16 * 320
              + d * 16 + 16 * 128 * d)
    index = 1024 * 64 * 128 + d * (128 + 64)
    expert = 3 * d * 1536
    sparse = d * 256 + expert + 8 * 8 / 256 * expert
    per_token = (2 * (full + index) + 3 * window + 3 * d * 13824 + 4 * sparse
                 + d * 19008)
    chosen = 2048 * 2049 / 2 + (S - 2048) * 2048
    windowed = 513 * 514 / 2 + (S - 513) * 513
    causal = S * (S + 1) / 2
    scores = (2 * 2 * 32 * (192 + 128) * chosen + 3 * 2 * 16 * (256 + 128) * windowed
              + 2 * 2 * 64 * 129 * causal)
    forward = B * (S * 2 * per_token + scores)
    assert flops_dots3_note.forward_flops(m, B, S) == pytest.approx(forward, rel=1e-12)
    assert chosen / causal == pytest.approx(0.234, abs=0.001)
    # the indexer's scores over the causal pairs: a sixth of the forward
    assert 2 * 2 * 64 * 129 * causal / forward == pytest.approx(0.166, abs=0.005)
    step = flops.train_step_flops({"family": "dots3_note", "model": m},
                                  {"batch": B, "seq_len": S})
    proj, idx = 2 * 2 * S * index, 2 * 2 * 64 * 129 * causal
    assert step == pytest.approx(3 * (forward - proj - idx) + 2 * proj + idx
                                 + 2 * 2 * 2 * 64 * 129 * chosen, rel=1e-12)


def test_the_parts_count_the_pairs_the_rule_keeps():
    m = _model()
    S = 16384
    peak = flops.peaks("TPU v5 lite")
    ops, moved = flops_dots3_note.index_step(m, 1, S)
    assert ops == 2 * (S * (S + 1) / 2) * 2 * 64 * 129
    assert moved > 2 * S * S / 8                      # the words, a layer each
    a_ops, _ = flops_dots3_note.attend_step(m, 1, S)
    chosen = 2048 * 2049 / 2 + (S - 2048) * 2048
    flash = sum((n_qk * 192 + n_v * 128) * 2 * chosen * 32
                for n_qk, n_v in ((1, 1), (2, 2), (2, 1)))
    assert a_ops == pytest.approx(2 * (flash + 3 * chosen * 2 * 64 * 129), rel=1e-12)
    s_ops, _ = flops_dots3_note.swa_step(m, 1, S)
    windowed = 513 * 514 / 2 + (S - 513) * 513
    assert s_ops == pytest.approx(3 * sum(
        (n_qk * 256 + n_v * 128) * 2 * windowed * 16
        for n_qk, n_v in ((1, 1), (2, 2), (2, 1))), rel=1e-12)
    for part in (flops_dots3_note.index_step, flops_dots3_note.attend_step,
                 flops_dots3_note.swa_step):
        assert flops.roofline_seconds(*part(m, 1, S), peak)[1] == "compute"


def _ctx(spans, devices=None, cell=CELL):
    c = run.load_cell(cell, rehearse=False)
    return readers.Context(c["config"], c["mix"], 1, spans, (10.0, 20.0), devices,
                           "_step_fn", flops.peaks("TPU v5 lite"))


def _metric(name):
    return json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())


def _made_up_trace(kernels):
    """Three runs of the step's module, two of them inside the cut."""
    ops, modules = [], []
    for step in range(3):
        t = 100.0 + step
        modules.append(("jit__step_fn(123)", t, 0.9))
        for name, dur in [("%fusion.9 = bf16[16384,5120]{1,0} fusion(%p)", 0.3),
                          *kernels]:
            ops.append((name, t, dur))
            t += dur
    return [trace.DeviceTrace("/device:TPU:0", ops, modules)]


def test_the_new_reader_and_metrics_on_a_made_up_trace():
    from benchmark.readers import dots3_roofline, kernel_time_share, span_attr_mean

    m, peak = _model(), flops.peaks("TPU v5 lite")
    least = {part: flops.roofline_seconds(*fn(m, 1, 16384), peak)[0]
             for part, fn in dots3_roofline.PARTS.items()}
    # each part's kernels at twice their least time a step
    kernels = [("%dsa_select.3 = (s32[1,4,16384,128]", 2 * least["index"]),
               ("%dsa_fwd.1 = bf16[1,32,16384,128]", least["attend"] / 2),
               ("%dsa_kl_dk.2 = f32[1,16384,128]", 3 * least["attend"] / 2),
               ("%swa_dkv.7 = (bf16[1,16,16384,256]", 2 * least["swa"])]
    c = _ctx([], _made_up_trace(kernels))
    for part, name in (("index", "dsa_index"), ("attend", "dsa_attend"),
                       ("swa", "swa")):
        roof = _metric(f"{name}_roofline.d3n")
        share = _metric(f"{name}_time_share.d3n")
        assert roof["reader"] == "dots3_roofline" and roof["args"]["part"] == part
        assert roof["layer"] == share["layer"]
        assert dots3_roofline.read(c, **roof["args"]) == pytest.approx(50.0)
        busy = 0.3 + sum(d for _, d in kernels)
        assert kernel_time_share.read(c, **share["args"]) == pytest.approx(
            100 * 2 * least[part] / busy)
        # nothing to read: no trace; a trace without the part (the parent has
        # no such program); another family's configuration
        assert dots3_roofline.read(_ctx([]), **roof["args"]) is None
        bare = _ctx([], _made_up_trace([]))
        assert dots3_roofline.read(bare, **roof["args"]) is None
        other = _ctx([], _made_up_trace(kernels), cell="joyai-mla-s8192")
        assert dots3_roofline.read(other, **roof["args"]) is None
    line = {"name": "step_metrics", "start": 15.0, "dur_s": 0.0,
            "attrs": {"dsa_kl": 0.25, "moe_load_max_over_mean": 1.5}}
    assert span_attr_mean.read(_ctx([line]), **_metric("dsa_kl.d3n")["args"]) == 0.25
    assert span_attr_mean.read(_ctx([]), **_metric("dsa_kl.d3n")["args"]) is None


def test_the_manifest_gives_the_cell_its_metrics():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "dots3-note-prev", "traffic": "b1-s16384",
                    "chips": 1}
    mine = sorted(x["name"] for x in m["per_layer"] if CELL in x["workloads"])
    assert mine == sorted(n + ".d3n" for n in (
        "step_mfu", "device_idle_share", "data_wait_share", "idle_in_data_wait",
        "idle_in_step_wait", "idle_in_step_dispatch", "idle_in_loop",
        "dsa_index_time_share", "dsa_index_roofline", "dsa_attend_time_share",
        "dsa_attend_roofline", "swa_time_share", "swa_roofline", "dsa_kl",
        "moe_load_max_over_mean", "moe_gmm_time_share", "moe_gmm_roofline"))
    for x in m["per_layer"]:
        if x["name"].endswith(".d3n"):
            assert x["workloads"] == [CELL]
            assert {k: _metric(x["name"])[k] for k in x} == x
    rate = next(x for x in m["end_to_end"] if x["name"] == "tokens_per_s_per_chip")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    check = run.load_cell(CELL, rehearse=False)["cell"]["check"]
    assert check["flash_kernel"] is True and check["limits"]["kernel_path_mismatch"] == 0
    assert check["limits"]["select_mismatch"] == 0
    assert set(check["limits_why"]) == set(compare.NUMBERS) | {"select_mismatch"}


def test_the_expert_layers_metrics_on_a_made_up_trace():
    """``moe_gmm_*.d3n`` read the held experts through ``.joy``'s reader: the
    least time of one grouped product over the step's ``moe_rows`` at the
    cell's widths (8 held experts of 1,536), the compiler's ``ragged-dot``
    events beside the chosen-key kernels."""
    from benchmark import flops_joyai_llm_flash
    from benchmark.readers import kernel_time_share, moe_gmm_roofline_joyai

    m, peak = _model(), flops.peaks("TPU v5 lite")
    ops, moved = flops_joyai_llm_flash.grouped_product(4096.0, m)
    assert (ops, moved) == (2.0 * 4096 * 5120 * 1536,
                            2.0 * (4096 * (5120 + 1536) + 8 * 5120 * 1536))
    least = flops.roofline_seconds(ops, moved, peak)[0]
    kernels = [("%ragged-dot-none.3 = bf16[4096,1536]", 4 * least),
               ("%ragged-dot-none.12 = bf16[4096,5120]", 4 * least),
               ("%dsa_fwd.1 = bf16[1,32,16384,128]", 0.01)]
    line = {"name": "step_metrics", "start": 15.0, "dur_s": 0.0,
            "attrs": {"moe_rows": 4096.0}}
    c = _ctx([line], _made_up_trace(kernels))
    roof, share = _metric("moe_gmm_roofline.d3n"), _metric("moe_gmm_time_share.d3n")
    assert roof["reader"] == "moe_gmm_roofline_joyai"
    assert moe_gmm_roofline_joyai.read(c, **roof["args"]) == pytest.approx(25.0)
    busy = 0.3 + sum(d for _, d in kernels)
    assert kernel_time_share.read(c, **share["args"]) == pytest.approx(
        100 * 8 * least / busy)
    assert moe_gmm_roofline_joyai.read(_ctx([], _made_up_trace(kernels)),
                                       **roof["args"]) is None
