"""The cell ``joyai-mla-s8192``: its files, the program against the plain
reference on seeded weights (and the reference with its router bent, which has
to differ), the rehearsal end to end, the control and the fault under the
cell's own limits, the counts of operations and bytes by hand, and the two new
readers on a made-up trace."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (compare, families, flops, flops_joyai_llm_flash, readers,
                       run, weights)
from benchmark.readers import trace
from benchmark.reference import joyai_llm_flash, train
from benchmark.reference.numerics import Numerics

sys.path.insert(0, str(Path(__file__).parent))
from test_bm_harness import last_line  # noqa: E402
from test_bm_harness import run as run_cell  # noqa: E402

CELL = "joyai-mla-s8192"
ROOT = Path(__file__).resolve().parents[2]
# the catalog's ``config`` for JoyAI-LLM-Flash, key for key
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16160}


def _batches(c, seed, n=3):
    from benchmark import traffic

    mix, model = c["mix"], c["config"]["model"]
    rows = traffic.make_records(mix, model, seed)[: n * mix["shape"]["batch"]]
    b = mix["shape"]["batch"]
    return [{k: np.stack([r[k] for r in rows[i * b:(i + 1) * b]]) for k in rows[0]}
            for i in range(n)]


def test_the_configuration_keeps_every_catalog_key_and_cuts_no_width():
    cfg = run.load_cell(CELL, rehearse=False)["config"]
    for k, v in CATALOG.items():
        assert cfg["model"][k] == REDUCED.get(k, v), k
    assert cfg["published"] == {k: CATALOG[k] for k in REDUCED}
    assert cfg["model"]["router_experts"] == CATALOG["n_routed_experts"]
    for meta in ("source", "assumed", "deployment", "job"):
        assert cfg[meta], meta
    entry = next(c for c in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "configs"] if c["name"] == "joyai-llm-flash")
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == cfg["source"]
    spec = joyai_llm_flash.param_spec(cfg["model"])
    n = sum(int(np.prod(s)) for s, _, _ in spec.values())
    assert n == cfg["model"]["parameters_as_built"] == 680_441_088   # 5.44 GB at 8 bytes
    # one embedding and one head serve both predictions
    assert sum(p.endswith("embedding") for p in spec) == 1
    assert sum(p.startswith("lm_head") for p in spec) == 1
    assert spec["layers/mlp/e_score_correction_bias"][0] == (4, 256)
    assert spec["mtp/block/mlp/experts/up_proj/kernel"][0] == (16, 2048, 768)


def _program_and_reference(route=None, monkeypatch=None):
    from tpucfn.mesh import MeshSpec, build_mesh

    c = run.load_cell(CELL, rehearse=True)
    config, model = c["config"], c["config"]["model"]
    spec = joyai_llm_flash.param_spec(model)
    mesh = build_mesh(MeshSpec.for_devices(1), jax.devices()[:1])
    trainer, items = families.load("joyai_llm_flash").build(config, c["mix"], mesh, None)
    assert items == 2 * 128
    params = weights.make(spec, weights.seed_key(7))
    batch = _batches(c, 11, n=1)[0]
    with jax.default_matmul_precision("highest"):
        (lp, (metrics, _)), gp = jax.value_and_grad(trainer.loss_fn, has_aux=True)(
            params, {}, batch, None)

        def reference():
            return jax.value_and_grad(lambda p: joyai_llm_flash.loss(
                model, config["job"], p, batch, Numerics()))(params)
    return spec, model, float(lp), weights.flatten(gp), metrics, reference


def _leaf_gaps(spec, got, want):
    return {p: float(jnp.max(jnp.abs(got[p] - want[p])))
            / (float(jnp.max(jnp.abs(want[p]))) + 1e-12)
            for p in spec if float(jnp.max(jnp.abs(want[p]))) > 0}


def test_the_program_matches_the_reference_loss_and_every_gradient_leaf():
    """The family's own build (the program as the cell runs it, float32 here)
    against ``benchmark/reference/joyai_llm_flash.py`` on the benchmark's
    seeded weights: the flash-dispatching attention against blocks of queries,
    sorted grouped products against the loop over experts, the chunked
    cross-entropies against the reference's."""
    spec, model, lp, gp, metrics, reference = _program_and_reference()
    lr, gr = reference()
    gr = weights.flatten(gr)
    assert lp == pytest.approx(float(lr), rel=1e-5)
    assert set(gp) == set(gr) == set(spec)
    for path in spec:
        assert gp[path].shape == tuple(spec[path][0]), path
        assert float(jnp.max(jnp.abs(gp[path] - gr[path]))) <= 2e-4 * float(
            jnp.max(jnp.abs(gr[path]))) + 1e-8, path
    # no gradient reaches the selection bias, on either side
    for path in spec:
        if path.endswith("e_score_correction_bias"):
            assert float(jnp.max(jnp.abs(gp[path]))) == 0.0 == float(
                jnp.max(jnp.abs(gr[path])))
    c = metrics["counters"]
    # 4 of 8 experts held, 2 a token: half the assignments on average
    assert 0.25 * 512 < float(c["moe_rows"]) < 0.75 * 512
    assert float(c["moe_dropped"]) == 0.0
    assert lp == pytest.approx(float(c["lm_loss"]) + model["mtp_lambda"] * float(
        c["mtp_loss"]), rel=1e-6)


@pytest.mark.parametrize("bent", ["bias_dropped", "weighed_by_the_biased_score"])
def test_a_reference_with_its_router_bent_does_not_match(bent, monkeypatch):
    """The bias is drawn wide enough to matter: a reference that drops it from
    the choice, or weighs by score plus bias, is off by far more than the
    program's agreement with the true one."""
    def route(model, x, p):
        s = jax.nn.sigmoid(jnp.einsum("td,de->te", x, p["router"]["kernel"],
                                      precision=jax.lax.Precision.HIGHEST))
        biased = s + p["e_score_correction_bias"]
        _, chosen = jax.lax.top_k(s if bent == "bias_dropped" else biased,
                                  model["num_experts_per_tok"])
        w = jnp.take_along_axis(s if bent == "bias_dropped" else biased, chosen, -1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen, w * model["routed_scaling_factor"]

    spec, model, lp, gp, _, reference = _program_and_reference()
    monkeypatch.setattr(joyai_llm_flash, "route", route)
    lr, gr = reference()
    gaps = _leaf_gaps(spec, gp, weights.flatten(gr))
    assert max(gaps.values()) > 1e-2, max(gaps.values())
    assert abs(lp - float(lr)) / float(lr) > 1e-6


@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_end_to_end(traced):
    proc = run_cell(["--workload", CELL, "--seed", str(2 ** 31 + 4321),
                     "--seconds", "1", "--trace", str(traced), "--rehearse"])
    out = last_line(proc)
    assert list(out)[-1] == "compared" and out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["input_mismatch"] == {"value": 0, "limit": 0}
    if traced:   # the program's spans alone: no CPU number under a device metric
        assert set(out["metrics"]) == {"data_wait_share.joy",
                                       "moe_load_max_over_mean.joy"}
        assert 1.0 <= out["metrics"]["moe_load_max_over_mean.joy"]["value"] < 4.0
    else:
        assert out["metrics"] == {}


def test_a_state_left_unchanged_reads_not_correct():
    proc = run_cell(["state_unchanged", "--workload", CELL, "--seed", "77",
                     "--seconds", "0.5", "--trace", "0", "--rehearse"],
                    script=(str(Path(__file__).parent / "fault_driver.py"),))
    out = last_line(proc)
    assert out["correct"] is False
    assert out["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_at_rehearsal_size_the_control_and_the_fault_read_not_correct():
    """The mechanics only: at rehearsal size the program computes in float32
    and sits on the reference, so any rounding separates.  Whether the cell's
    limits hold the control off at the cell's own size is read on the chip
    (``read_limits.py``; PERF.md, Findings, PR 31)."""
    c = run.load_cell(CELL, rehearse=True)
    limits = run.load_cell(CELL, rehearse=False)["cell"]["check"]["limits"]
    seed = 2 ** 31 + 5
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
    m = {**CATALOG, **REDUCED, "router_experts": 256}
    S, B = 8192, 2
    attn = (2048 * 1536 + 1536 * 32 * 192 + 2048 * (512 + 64)
            + 512 * 32 * (128 + 128) + 32 * 128 * 2048)
    assert flops_joyai_llm_flash.attention_macs(m) == attn == 26_345_472
    expert = 3 * 2048 * 768
    assert flops_joyai_llm_flash.expert_macs(m) == expert == 4_718_592
    assert flops_joyai_llm_flash.dense_ffn_macs(m) == 3 * 2048 * 7168 == 44_040_192
    # router, shared expert, and 8 * 16 / 256 = half a routed expert a token
    ffn = 2048 * 256 + expert + 0.5 * expert
    assert flops_joyai_llm_flash.sparse_ffn_macs(m) == ffn
    # six blocks (1 dense, 4 sparse, the prediction block), two head passes
    per_token = (6 * attn + 44_040_192 + 5 * ffn + 2 * 2048 * 16160
                 + 2 * 2048 * 2048)
    scores = 6 * S * S * 32 * (192 + 128)      # q k^T and p v, the half kept
    assert flops_joyai_llm_flash.attention_score_flops(m, S) == scores / 6
    forward = B * (S * 2 * per_token + scores)
    assert flops_joyai_llm_flash.forward_flops(m, B, S) == pytest.approx(forward, rel=1e-12)
    assert forward / (B * S) == pytest.approx(1.133e9, rel=2e-3)   # a token, forward
    assert B * scores / forward == pytest.approx(0.444, rel=5e-3)   # the scores' share
    step = flops.train_step_flops({"family": "joyai_llm_flash", "model": m},
                                  {"batch": B, "seq_len": S})
    assert step == 3 * forward
    assert step == pytest.approx(55.7e12, rel=2e-3)   # 0.283 s at the chip's peak


@pytest.mark.parametrize("kind", ["fwd", "dkv", "dq"])
def test_a_flash_call_counts_the_keys_and_the_values_own_sizes(kind):
    B, S, H = 2, 8192, 32
    ops, moved = flops_joyai_llm_flash.flash_call(kind, B, S, H, H, 192, 128)
    products = {"fwd": 192 + 128, "dkv": 2 * 192 + 2 * 128, "dq": 2 * 192 + 128}
    assert ops == products[kind] * S * S * H * B
    q = k = B * S * H * 192 * 2
    o = v = B * S * H * 128 * 2
    lse = B * S * H * 4
    assert moved == {"fwd": q + k + v + o + lse,
                     "dkv": q + 2 * o + 2 * k + 2 * v + 2 * lse,
                     "dq": 2 * q + 2 * o + k + v + 2 * lse}[kind]
    # compute-bound on the chip, and equal sizes count as flops.flash_call does
    assert flops.roofline_seconds(ops, moved, flops.peaks("TPU v5 lite"))[1] == "compute"
    assert flops_joyai_llm_flash.flash_call(kind, B, S, 16, 2, 256, 256) \
        == flops.flash_call(kind, B, S, 16, 2, 256)


def test_a_grouped_product_counts_the_experts_held():
    m = {**CATALOG, **REDUCED}
    ops, moved = flops_joyai_llm_flash.grouped_product(8192, m)
    assert ops == 2 * 8192 * 2048 * 768
    assert moved == 2 * (8192 * (2048 + 768) + 16 * 2048 * 768)
    assert flops.roofline_seconds(ops, moved, flops.peaks("TPU v5 lite"))[1] == "compute"


def _ctx(spans, devices=None, host_interval=(10.0, 20.0), cell=CELL):
    c = run.load_cell(cell, rehearse=False)
    return readers.Context(c["config"], c["mix"], 1, spans, host_interval, devices,
                           "_step_fn", flops.peaks("TPU v5 lite"))


def _metric(name):
    return json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())


def _made_up_trace(fwd_s, dkv_s, dq_s, gmm_s):
    """Three runs of the step's module, two of them inside the cut; a step:
    two forward calls, one of each backward kernel, four grouped products."""
    ops, modules = [], []
    for step in range(3):
        t = 100.0 + step
        modules.append(("jit__step_fn(123)", t, 0.9))
        for name, dur in (("%flash_fwd.1 = bf16[2,32,8192,128]", fwd_s),
                          ("%flash_fwd.2 = bf16[2,32,8192,128]", fwd_s),
                          ("%flash_dkv.3 = (bf16[2,32,8192,192]", dkv_s),
                          ("%flash_dq.4 = bf16[2,32,8192,192]", dq_s),
                          *[(f"%ragged-dot-none.{i} = bf16[8192,768]", gmm_s)
                            for i in range(4)],
                          ("%fusion.9 = f32[2,8192,2048]", 0.1)):
            ops.append((name, t, dur))
            t += dur
    return [trace.DeviceTrace("/device:TPU:0", ops, modules)]


def test_the_new_readers_on_a_made_up_trace():
    from benchmark.readers import (flash_roofline_joyai, kernel_time_share,
                                   moe_gmm_roofline_joyai)

    peak = flops.peaks("TPU v5 lite")
    least = {k: flops.roofline_seconds(*flops_joyai_llm_flash.flash_call(
        k, 2, 8192, 32, 32, 192, 128), peak)[0] for k in ("fwd", "dkv", "dq")}
    assert least["fwd"] == pytest.approx(320 * 8192 * 8192 * 64 / 197e12, rel=1e-6)
    # every kernel at twice its least time: half its roofline
    devs = _made_up_trace(2 * least["fwd"], 2 * least["dkv"], 2 * least["dq"], 0.001)
    line = {"name": "step_metrics", "start": 15.0, "dur_s": 0.0,
            "attrs": {"moe_rows": 8200.0, "moe_load_max_over_mean": 1.5}}
    c = _ctx([line], devs)
    m = _metric("flash_roofline.joy")
    assert m["reader"] == "flash_roofline_joyai" and m["workloads"] == [CELL]
    assert flash_roofline_joyai.read(c, **m["args"]) == pytest.approx(50.0)
    g = _metric("moe_gmm_roofline.joy")
    assert g["reader"] == "moe_gmm_roofline_joyai"
    one, _ = flops.roofline_seconds(
        *flops_joyai_llm_flash.grouped_product(8200.0, c.config["model"]), peak)
    assert moe_gmm_roofline_joyai.read(c, **g["args"]) == pytest.approx(
        100 * one / 0.001)
    share = kernel_time_share.read(c, **_metric("flash_time_share.joy")["args"])
    parts = sum(kernel_time_share.read(c, **_metric(f"flash_{k}_time_share.joy")["args"])
                for k in ("fwd", "dkv", "dq"))
    assert 0 < share < 100 and parts == pytest.approx(share)
    # nothing to read: no trace, no step_metrics line (the parent writes
    # none), another family's configuration, a trace without the kernels
    assert flash_roofline_joyai.read(_ctx([line]), **m["args"]) is None
    assert moe_gmm_roofline_joyai.read(_ctx([], devs), **g["args"]) is None
    other = _ctx([line], devs, cell="qwen3next-ep8-s8192")
    assert flash_roofline_joyai.read(other, **m["args"]) is None
    assert moe_gmm_roofline_joyai.read(other, **g["args"]) is None
    bare = [trace.DeviceTrace(d.name, [e for e in d.ops if "fusion" in e[0]],
                              d.modules) for d in devs]
    assert flash_roofline_joyai.read(_ctx([line], bare), **m["args"]) is None
    assert moe_gmm_roofline_joyai.read(_ctx([line], bare), **g["args"]) is None
    # a kernel that pads 192 to 256 takes 4/3 of the time on the scores'
    # products: the share counts the model's sizes and reads lower, never higher
    slow = _made_up_trace(least["fwd"] * 384 / 320, least["dkv"] * 768 / 640,
                          least["dq"] * 640 / 512, 0.001)
    assert 80 < flash_roofline_joyai.read(_ctx([line], slow), **m["args"]) < 100


def test_the_manifest_gives_the_cell_its_metrics():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "joyai-llm-flash", "traffic": "b2-s8192",
                    "chips": 1}
    mine = sorted(x["name"] for x in m["per_layer"] if CELL in x["workloads"])
    assert mine == sorted(n + ".joy" for n in (
        "step_mfu", "device_idle_share", "data_wait_share", "idle_in_data_wait",
        "idle_in_step_wait", "idle_in_step_dispatch", "idle_in_loop",
        "flash_roofline", "flash_time_share", "flash_fwd_time_share",
        "flash_dkv_time_share", "flash_dq_time_share", "moe_gmm_time_share",
        "moe_gmm_roofline", "moe_load_max_over_mean"))
    assert all(x["workloads"] == [CELL] for x in m["per_layer"]
               if x["name"].endswith(".joy"))
    rate = next(x for x in m["end_to_end"] if x["name"] == "tokens_per_s_per_chip")
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.01
    check = run.load_cell(CELL, rehearse=False)["cell"]["check"]
    assert check["flash_kernel"] is True and check["limits"]["kernel_path_mismatch"] == 0
