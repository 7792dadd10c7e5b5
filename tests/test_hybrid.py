"""``models/hybrid.py``: the period-scanned decoder.  Its two mixers against
the plain reference's, the stacked tree and its sharding rules, training
through ``Trainer`` on one and on several devices, the step's routing
counters in trace and gauges, and the refusal to serve or convert."""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpucfn.mesh import MeshSpec, build_mesh
from tpucfn.models.hybrid import (GatedAttention, GatedDeltaNet,
                                  HybridConfig, HybridDecoder, make_loss_fn,
                                  sharding_rules)
from tpucfn.ops.attention import dot_product_attention
from tpucfn.parallel import shard_batch
from tpucfn.train import Trainer

CFG = HybridConfig.tiny()
MODEL = {"head_dim": 16, "rms_norm_eps": 1e-6, "partial_rotary_factor": 0.25,
         "rope_theta": 1e7, "linear_num_key_heads": 2,
         "linear_num_value_heads": 4, "linear_key_head_dim": 16,
         "linear_value_head_dim": 16}


def _against_reference(module, ref_fn, seed):
    from benchmark.reference.numerics import Numerics

    x = jax.random.normal(jax.random.key(seed), (2, 24, CFG.dim))
    params = module.init(jax.random.key(seed + 1), x)["params"]
    # away from the initial values: norm weights at 0, A_log anywhere
    params = jax.tree.map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(a.size), a.shape),
        params)

    def apply(p, x):   # a DeltaNet layer returns its readings beside its output
        out = module.apply({"params": p}, x)
        return out[0] if isinstance(out, tuple) else out

    with jax.default_matmul_precision("highest"):
        out = apply(params, x)
        ref = ref_fn(MODEL, Numerics(), x, params)
        f = lambda fn: lambda p, x: jnp.sum(jnp.sin(fn(p, x)))  # noqa: E731
        got = jax.grad(f(apply), (0, 1))(params, x)
        want = jax.grad(f(lambda p, x: ref_fn(MODEL, Numerics(), x, p)), (0, 1))(params, x)
    # float32 on both sides; the orders of summation differ
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5 * float(jnp.max(jnp.abs(ref)))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b))) + 1e-7


def test_gated_attention_matches_the_reference():
    """Partial rotary embedding (4 of 16 dims turn), zero-centred norm a head
    on q and k, the sigmoid gate from the wide query projection, 4 query
    heads on 2 key heads."""
    from benchmark.reference import qwen3_next as ref

    _against_reference(GatedAttention(CFG, dot_product_attention),
                       ref.gated_attention, 0)


def test_gated_delta_net_matches_the_reference():
    """The chunked recurrence inside its layer (convolution, unit q and k,
    beta and decay, the gated norm) against the reference's position by
    position; 24 positions over chunks of 16."""
    from benchmark.reference import qwen3_next as ref

    _against_reference(GatedDeltaNet.of(CFG), ref.gated_delta_net, 10)


def test_the_tree_is_stacked_by_period_and_the_rules_fit_it():
    cfg = dataclasses.replace(CFG, n_layers=8, held_experts=(2, 4))
    net = HybridDecoder(cfg)
    tree = jax.eval_shape(lambda: net.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    lin, full = tree["periods"]["linear"], tree["periods"]["full"]
    assert lin["mixer"]["q_proj"]["kernel"].shape == (2, 3, 64, 32)
    assert lin["mlp"]["experts"]["gate_proj"]["kernel"].shape == (2, 3, 4, 64, 32)
    assert lin["mlp"]["router"]["kernel"].shape == (2, 3, 64, 8)   # all 8 scored
    assert full["mixer"]["q_proj"]["kernel"].shape == (2, 64, 4 * 2 * 16)
    assert full["mlp"]["experts"]["down_proj"]["kernel"].shape == (2, 4, 32, 64)
    rules = sharding_rules(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        name = "/".join(k.key for k in path)
        spec = rules.spec_for(name, leaf.ndim)      # raises if over-long
        if name.endswith("kernel") and "conv" not in name:
            assert "fsdp" in spec, name
            axis = spec.index("fsdp")
            assert leaf.shape[axis] % 2 == 0 or leaf.shape[axis] == 1, name
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, n_layers=6)


def _trainer(mesh, cfg=CFG, tx=None):
    net = HybridDecoder(cfg)

    def init_fn(rng):
        return net.init(rng, jnp.zeros((8, 32), jnp.int32))["params"], {}

    return Trainer(mesh, sharding_rules(cfg),
                   make_loss_fn(net, ce_chunk=16), tx or optax.adafactor(1e-2),
                   init_fn)


def test_it_trains_through_the_trainer_and_counts_its_routing():
    mesh = build_mesh(MeshSpec.for_devices(1), jax.devices()[:1])
    trainer = _trainer(mesh)
    state = trainer.init(jax.random.key(0))
    tokens = np.random.RandomState(0).randint(0, 256, (8, 32)).astype(np.int32)
    batch = shard_batch(mesh, {"tokens": tokens})
    losses = []
    for _ in range(8):
        state, m = trainer.step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    c = m["counters"]
    assert set(c) == {"moe_rows", "moe_load_max_over_mean", "moe_dropped",
                      "moe_blocks_run"}
    # all 8 experts held: every assignment falls here, none is lost
    assert float(c["moe_rows"]) == 8 * 32 * CFG.top_k
    assert float(c["moe_dropped"]) == 0.0
    assert float(c["moe_load_max_over_mean"]) >= 1.0
    assert float(c["moe_blocks_run"]) == 1.0     # all held: one block is all


def test_a_mesh_with_fsdp_gives_the_one_chip_losses():
    one = build_mesh(MeshSpec.for_devices(1), jax.devices()[:1])
    four = build_mesh(MeshSpec.for_devices(4, fsdp=2), jax.devices()[:4])
    tokens = np.random.RandomState(1).randint(0, 256, (8, 32)).astype(np.int32)
    out = []
    for mesh in (one, four):
        # Adam: Adafactor's unfactored placeholders (dims under 128 here)
        # share the kernels' paths and do not divide over fsdp
        trainer = _trainer(mesh, tx=optax.adam(1e-3))
        state = trainer.init(jax.random.key(3))
        if mesh is four:
            k = state.params["periods"]["linear"]["mixer"]["q_proj"]["kernel"]
            assert k.sharding.spec == jax.sharding.PartitionSpec(None, None, "fsdp")
        batch = shard_batch(mesh, {"tokens": tokens})
        run = []
        for _ in range(3):
            state, m = trainer.step(state, batch)
            run.append(float(m["loss"]))
        out.append(run)
    np.testing.assert_allclose(out[0], out[1], rtol=2e-5)


def test_the_loop_writes_the_counters_to_trace_and_gauges(tmp_path):
    from tpucfn.obs import Tracer
    from tpucfn.obs.registry import MetricRegistry
    from tpucfn.train.trainer import TrainerObs

    reg = MetricRegistry()
    tracer = Tracer(tmp_path, host_id=0, role="trainer")
    obs = TrainerObs(reg, tracer)
    obs.record_step_counters(7, {"moe_rows": 20480.0, "moe_dropped": 0.0,
                                 "moe_load_max_over_mean": 1.5})
    tracer.close()
    rows = [json.loads(ln) for p in tmp_path.glob("trace-*.jsonl")
            for ln in p.read_text().splitlines()]
    (row,) = [r for r in rows if r["name"] == "step_metrics"]
    assert row["kind"] == "span" and row["trace_id"] == 7 and row["dur_s"] == 0.0
    assert row["attrs"] == {"moe_rows": 20480.0, "moe_dropped": 0.0,
                            "moe_load_max_over_mean": 1.5}
    text = reg.to_prometheus()
    assert "train_moe_rows 20480" in text
    assert "train_moe_load_max_over_mean 1.5" in text
    assert "train_moe_dropped 0" in text


@pytest.mark.parametrize("what", ["model", "config", "published"])
def test_serving_and_conversion_refuse_it_by_name(what):
    from tpucfn.models.hf_convert import config_from_hf
    from tpucfn.serve.engine import ServeEngine

    with pytest.raises(NotImplementedError, match="recurrent state"):
        if what == "model":
            ServeEngine(HybridDecoder(CFG), {}, max_batch=1, cache_len=8)
        elif what == "config":
            ServeEngine.from_llama(CFG, {})
        else:
            config_from_hf(types.SimpleNamespace(model_type="qwen3_next"))
