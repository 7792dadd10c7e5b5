"""The cell ``qwen3next-ep8-s8192``: the program against the plain reference
on seeded weights, the rehearsal end to end, the control and the fault under
the cell's own limits, the count of operations by hand, and the new readers."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, families, flops, flops_qwen3_next, readers, run, weights
from benchmark.reference import qwen3_next, train
from benchmark.reference.numerics import Numerics

sys.path.insert(0, str(Path(__file__).parent))
from test_bm_harness import last_line  # noqa: E402
from test_bm_harness import run as run_cell  # noqa: E402

CELL = "qwen3next-ep8-s8192"
ROOT = Path(__file__).resolve().parents[2]
PUBLISHED = {"hidden_size": 2048, "num_hidden_layers": 4, "full_attention_interval": 4,
             "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 256,
             "linear_num_key_heads": 16, "linear_num_value_heads": 32,
             "linear_key_head_dim": 128, "linear_value_head_dim": 128,
             "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
             "shared_expert_intermediate_size": 512, "router_experts": 512,
             "num_experts": 64, "num_experts_per_tok": 10, "vocab_size": 18992}


def _batches(c, seed, n=3):
    from benchmark import traffic

    mix, model = c["mix"], c["config"]["model"]
    rows = traffic.make_records(mix, model, seed)[: n * mix["shape"]["batch"]]
    b = mix["shape"]["batch"]
    return [{k: np.stack([r[k] for r in rows[i * b:(i + 1) * b]]) for k in rows[0]}
            for i in range(n)]


def test_the_configuration_keeps_the_published_sizes():
    cfg = run.load_cell(CELL, rehearse=False)["config"]
    for k, v in PUBLISHED.items():
        assert cfg["model"][k] == v, k
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    entry = next(c for c in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "configs"] if c["name"] == "qwen3-next-80b-a3b")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    spec = qwen3_next.param_spec(cfg["model"])
    n = sum(int(np.prod(s)) for s, _, _ in spec.values())
    assert n == pytest.approx(1028.3e6, rel=1e-4)       # 8.2 GB at 8 bytes


def test_the_program_matches_the_reference_loss_and_every_gradient_leaf():
    """The family's own build (the program as the cell runs it, float32 here)
    against ``benchmark/reference/qwen3_next.py`` on the benchmark's seeded
    weights: the chunked recurrence against position by position, grouped
    products against the loop over experts, the program's cross-entropy
    against the reference's."""
    from tpucfn.mesh import MeshSpec, build_mesh

    c = run.load_cell(CELL, rehearse=True)
    config, model = c["config"], c["config"]["model"]
    spec = qwen3_next.param_spec(model)
    mesh = build_mesh(MeshSpec.for_devices(1), jax.devices()[:1])
    trainer, items = families.load("qwen3_next").build(config, c["mix"], mesh, None)
    assert items == 2 * 128
    params = weights.make(spec, weights.seed_key(7))
    batch = _batches(c, 11, n=1)[0]
    with jax.default_matmul_precision("highest"):
        (lp, (metrics, _)), gp = jax.value_and_grad(trainer.loss_fn, has_aux=True)(
            params, {}, batch, None)
        lr, gr = jax.value_and_grad(lambda p: qwen3_next.loss(
            model, config["job"], p, batch, Numerics()))(params)
    # float32 on both sides, every product in all passes: what is left is the
    # order of summation (chunks of 64 against single positions, groups of
    # rows against masked sums over all tokens)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    flat_p, flat_r = weights.flatten(gp), weights.flatten(gr)
    assert set(flat_p) == set(flat_r) == set(spec)
    for path in spec:
        a, b = flat_p[path], flat_r[path]
        assert a.shape == tuple(spec[path][0]), path
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * float(jnp.max(jnp.abs(b))) + 1e-8, path
    # 4 of 8 experts held, 2 a token: half the assignments on average
    assert 0.25 * 512 < float(metrics["counters"]["moe_rows"]) < 0.75 * 512
    assert float(metrics["counters"]["moe_dropped"]) == 0.0


@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_end_to_end(traced):
    proc = run_cell(["--workload", CELL, "--seed", str(2 ** 31 + 4321),
                     "--seconds", "1", "--trace", str(traced), "--rehearse"])
    out = last_line(proc)
    assert list(out)[-1] == "compared" and out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["input_mismatch"] == {"value": 0, "limit": 0}
    if traced:   # the program's spans alone: no CPU number under a device metric
        assert set(out["metrics"]) == {"data_wait_share.q3n",
                                       "moe_load_max_over_mean.q3n"}
        assert 1.0 <= out["metrics"]["moe_load_max_over_mean.q3n"]["value"] < 4.0
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
    limits hold the control and the fault off at the cell's own size is read
    on the chip (``read_limits.py``; PERF.md, Findings, PR 27)."""
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


def test_the_control_holds_activations_in_fp8_and_float32_holds_them_as_they_are():
    """``_held`` marks every tensor the program holds in bfloat16: nothing in
    float32; in the control the e4m3 grid scaled to the tensor's largest
    magnitude, the backward pass the identity."""
    x = jax.random.normal(jax.random.key(3), (4, 33)) * 0.02
    assert jnp.array_equal(qwen3_next._held(Numerics(), x), x)
    held = qwen3_next._held(Numerics("fp8"), x)
    scale = float(jnp.max(jnp.abs(x))) / 448.0
    grid = (held / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    assert float(jnp.max(jnp.abs(grid - held))) <= 1e-9
    err = jnp.abs(held - x) / jnp.maximum(jnp.abs(x), scale * 2.0 ** -6)
    assert 1e-3 < float(jnp.max(err)) <= 2.0 ** -4 + 1e-6   # 3 bits of mantissa
    g = jax.grad(lambda x: jnp.sum(qwen3_next._held(Numerics("fp8"), x) ** 2))(x)
    assert jnp.allclose(g, 2 * held)


def test_step_flops_of_one_period_by_hand():
    m = PUBLISHED
    S, B = 8192, 2
    delta = 2048 * (2048 + 2048 + 4096 + 4096 + 32 + 32) + 4096 * 2048 + 4 * 8192
    assert flops_qwen3_next.delta_layer_macs(m) == delta
    assert delta == pytest.approx(33.72e6, rel=1e-3)
    attn = 2048 * 16 * 512 + 2 * 2048 * 2 * 256 + 16 * 256 * 2048
    assert flops_qwen3_next.attention_layer_macs(m) == attn == 27_262_976
    expert = 3 * 2048 * 512
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * 64 / 512 * expert
    assert flops_qwen3_next.ffn_macs(m) == ffn
    state = 3 * 32 * 128 * 128
    assert flops_qwen3_next.delta_rule_macs(m) == state
    per_token = 3 * (delta + state) + attn + 4 * ffn + 2048 * 18992
    assert per_token == pytest.approx(204.6e6, rel=1e-3)   # 199.8 M + the scan's 4.7 M
    causal = 2 * 2 * S * S * 256 * 16 / 2                  # QK^T and PV, the half kept
    forward = B * (S * 2 * per_token + causal)
    assert flops_qwen3_next.forward_flops(m, B, S) == pytest.approx(forward, rel=1e-12)
    step = flops.train_step_flops({"family": "qwen3_next", "model": m},
                                  {"batch": B, "seq_len": S})
    assert step == 3 * flops_qwen3_next.forward_flops(m, B, S)
    assert step == pytest.approx(23.4e12, rel=5e-3)        # the issue's "about 24"
    # a grouped product over 20,480 rows moves more than it multiplies for
    ops, moved = flops_qwen3_next.grouped_product(20480, m)
    assert ops == 2 * 20480 * 2048 * 512
    assert moved == 2 * (20480 * (2048 + 512) + 64 * 2048 * 512)
    assert flops.roofline_seconds(ops, moved, flops.peaks("TPU v5 lite"))[1] == "memory"


def _ctx(spans, devices=None, host_interval=(10.0, 20.0)):
    c = run.load_cell(CELL, rehearse=False)
    return readers.Context(c["config"], c["mix"], 1, spans, host_interval, devices,
                           "_step_fn", flops.peaks("TPU v5 lite"))


def _metric(name):
    return json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())


def test_the_counters_are_read_from_the_step_metrics_lines():
    from benchmark.readers import span_attr_mean

    line = lambda t, **a: {"name": "step_metrics", "start": t, "dur_s": 0.0,  # noqa: E731
                           "attrs": a}
    spans = [line(5.0, moe_load_max_over_mean=9.0),       # before the trace
             line(11.0, moe_load_max_over_mean=1.2, moe_rows=20000.0),
             line(12.0, moe_load_max_over_mean=1.4, moe_rows=21000.0),
             {"name": "step", "start": 11.0, "dur_s": 0.5}]
    m = _metric("moe_load_max_over_mean.q3n")
    assert m["reader"] == "span_attr_mean" and m["source"] == "program_span"
    assert span_attr_mean.read(_ctx(spans), **m["args"]) == pytest.approx(1.3)
    # a program that writes no such line (the parent): nothing, and no error
    assert span_attr_mean.read(_ctx(spans[-1:]), **m["args"]) is None
    assert span_attr_mean.read(_ctx(spans, host_interval=None), **m["args"]) is None
    from benchmark.readers import moe_gmm_roofline

    assert moe_gmm_roofline.read(_ctx(spans[-1:]), **_metric(
        "moe_gmm_roofline.q3n")["args"]) is None


def test_the_patterns_read_a_recorded_sample_from_the_chip():
    """Two steps of the cell on the v5e chip (my chip run, PR 27, call 2),
    reduced to the events the cell's patterns name and the 150 longest others:
    the flash kernels by their names, the grouped products by the name the
    compiler gives ``ragged_dot``'s kernel, the delta rule's scan by the
    ``while`` whose carry is its float32 state, and the whole rule by the
    chunk tensors its operations hold (the 100 longest of them are kept)."""
    import re

    from benchmark.readers import (flash_roofline, gdn_time_share,
                                   kernel_time_share, moe_gmm_roofline, trace)

    devs = trace.load_saved(Path(__file__).parent / "data" / "q3n_trace_sample.json.gz")
    (t0, t1, steps) = trace.step_interval(devs[0], "_step_fn")
    assert steps == 2
    names = [n for n, _, _ in trace.clip(devs[0].ops, t0, t1)]
    count = lambda name: sum(bool(re.search(  # noqa: E731
        _metric(name)["args"]["pattern"], n)) for n in names)
    # a step: forward, key/value backward, query backward (with one period the
    # loop over periods is gone and the compiler merges remat's forward)
    assert count("flash_time_share.q3n") == 2 * 3
    # a layer a step: three products forward, three again for remat, six
    # backward (rows and weights); four layers, one block of rows active
    assert count("moe_gmm_time_share.q3n") == 2 * 4 * 12
    # a DeltaNet layer a step: the scan forward three times (the layer's remat
    # and the rule's own) and once backward; three such layers
    assert count("gdn_scan_time_share.q3n") == 2 * 3 * 4
    line = {"name": "step_metrics", "start": 15.0, "dur_s": 0.0,
            "attrs": {"moe_rows": 20600.0}}
    c = _ctx([line], devs)
    share = {}
    for name in ("flash_time_share.q3n", "moe_gmm_time_share.q3n",
                 "gdn_scan_time_share.q3n", "flash_fwd_time_share.q3n",
                 "flash_dkv_time_share.q3n", "flash_dq_time_share.q3n"):
        m = _metric(name)
        assert m["reader"] == "kernel_time_share"
        share[name] = kernel_time_share.read(c, **m["args"])
        assert 0 < share[name] < 100
    assert sum(share[f"flash_{k}_time_share.q3n"] for k in ("fwd", "dkv", "dq")
               ) == pytest.approx(share["flash_time_share.q3n"])
    # the whole rule: the scans and the operations that hold a chunk tensor,
    # from the configuration's sizes; a loop over layers belongs to nothing
    whole = _metric("gdn_time_share.q3n")
    assert whole["reader"] == "gdn_time_share" and whole["args"] == {"chunk": 64}
    assert share["gdn_scan_time_share.q3n"] < gdn_time_share.read(c, **whole["args"]) < 100
    assert gdn_time_share.holds("%fusion.1 = f32[2,16,2,128,64,64]{5,4} fusion(", (2, 16, 128, 64))
    assert gdn_time_share.holds("%copy.3 = f32[2,128,64,16,2,128]{5,2,1} copy(", (2, 16, 128, 64))
    assert not gdn_time_share.holds("%fusion.2 = bf16[2,16,8192,256]{3,2} fusion(bf16[16,64])", (2, 16, 128, 64))
    assert gdn_time_share.CONTROL.search("%while.556 = (s32[]{:T(128)}, bf16[2,16,128,64]")
    # another program's trace (no chunk tensor, no such scan): nothing to read
    other = trace.DeviceTrace(devs[0].name, [
        (n.replace("128,64", "1,1").replace("2,16,2,128,128", "1"), s, d)
        for n, s, d in devs[0].ops], devs[0].modules)
    assert gdn_time_share.read(_ctx([line], [other]), **whole["args"]) is None
    flash = flash_roofline.read(c, **_metric("flash_roofline.q3n")["args"])
    gmm = moe_gmm_roofline.read(c, **_metric("moe_gmm_roofline.q3n")["args"])
    # shares of a roofline: above a few percent, never past 100
    assert 20 < flash < 100 and 5 < gmm < 100


def test_the_manifest_gives_the_cell_its_metrics():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "b2-s8192"
    mix = json.loads((ROOT / "benchmark/traffic/b2-s8192.json").read_text())
    assert mix["shape"] == {"batch": 2, "seq_len": 8192}
    mine = sorted(x["name"] for x in m["per_layer"] if CELL in x["workloads"])
    assert mine == sorted(n + ".q3n" for n in (
        "step_mfu", "device_idle_share", "data_wait_share", "flash_roofline",
        "flash_time_share", "gdn_time_share", "moe_gmm_time_share",
        "moe_gmm_roofline", "moe_load_max_over_mean",
        # after review: the idle time by the host's phase, the flash share by
        # kernel, the rule's scan beside the whole rule
        "idle_in_data_wait", "idle_in_step_wait", "idle_in_step_dispatch",
        "idle_in_loop", "flash_fwd_time_share", "flash_dkv_time_share",
        "flash_dq_time_share", "gdn_scan_time_share"))
    assert all(x["workloads"] == [CELL] for x in m["per_layer"] if x["name"].endswith(".q3n"))
    rate = next(x for x in m["end_to_end"] if x["name"] == "tokens_per_s_per_chip")
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.01
