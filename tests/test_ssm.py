"""The state-space decoder (``models/ssm.py``): the model against the plain
reference ``benchmark/reference/granite4_h.py`` on seeded weights at a small
size (float32; hidden 64, 4 heads of 16, state 16, chunk 8, ten layers with the
attention layer sixth), each departure from the published equations failing
that comparison, the tied head, the tree and its rules, the trainer."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import weights
from benchmark.reference import granite4_h
from benchmark.reference.numerics import Numerics
from tpucfn.mesh import MeshSpec, build_mesh
from tpucfn.models.layers import apply_rope, rope_frequencies
from tpucfn.models.llama import chunked_causal_lm_loss
from tpucfn.models.ssm import (PERIOD, SSMConfig, SSMDecoder, make_loss_fn,
                               sharding_rules)
from tpucfn.ops.attention import dot_product_attention
from tpucfn.parallel import shard_batch
from tpucfn.train import Trainer

CFG = SSMConfig.tiny()
# the same sizes under their published names; weights drawn wide enough that
# every multiplier moves the loss
MODEL = {
    "hidden_size": 64, "num_hidden_layers": 10, "layer_types": list(PERIOD),
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "shared_intermediate_size": 128, "num_local_experts": 0,
    "tie_word_embeddings": True, "vocab_size": 256, "mamba_n_heads": 8,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 8,
    "initializer_range": 0.1, "a_log_mean": 1.386, "a_log_std": 0.693,
    "dt_bias_mean": -3.0, "dt_bias_std": 1.0, "conv_bias_std": 0.29}
SPEC = granite4_h.param_spec(MODEL)
PARAMS = weights.make(SPEC, weights.seed_key(7))
TOKENS = np.asarray(jax.random.randint(jax.random.key(3), (2, 37), 0, 256))


def _program(cfg=CFG, attention_fn=None):
    """(loss, flat gradients, metrics) of the program's own loss function."""
    loss_fn = make_loss_fn(SSMDecoder(cfg, attention_fn=attention_fn), ce_chunk=16)
    with jax.default_matmul_precision("highest"):
        (loss, (metrics, _)), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True), static_argnums=3)(PARAMS, {}, {"tokens": TOKENS}, None)
    return float(loss), weights.flatten(grads), metrics


@pytest.fixture(scope="module")
def reference():
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: granite4_h.loss(
            MODEL, {}, p, {"tokens": TOKENS}, Numerics())))(PARAMS)
    return float(loss), weights.flatten(grads)


def _worst_gap(got, want):
    return max(float(jnp.max(jnp.abs(got[p] - want[p])))
               / (float(jnp.max(jnp.abs(want[p]))) + 1e-12) for p in SPEC)


def test_the_model_matches_the_reference_loss_and_every_gradient_leaf(reference):
    """The chunked op against the recurrence token by token, the dispatching
    attention at the model's scale against blocks of queries, the chunked
    cross-entropy over the tied table against the reference's."""
    loss, grads, metrics = _program()
    want_loss, want = reference
    assert loss == pytest.approx(want_loss, rel=1e-6)
    assert set(grads) == set(want) == set(SPEC)
    for path in SPEC:
        assert grads[path].shape == tuple(SPEC[path][0]), path
        assert float(jnp.max(jnp.abs(want[path]))) > 0, path
    assert _worst_gap(grads, want) < 2e-5      # float32 round-off: 1.4e-6 read
    c = metrics["counters"]
    assert set(c) == {"ssm_log_decay_min", "ssm_state_rms"}
    assert float(c["ssm_log_decay_min"]) < 0 < float(c["ssm_state_rms"])


def _with_rotary(q, k, v, *, causal=True, scale=None, **_):
    cos, sin = rope_frequencies(q.shape[-1], q.shape[1], 10000.0)
    turn = lambda t: apply_rope(t, cos, sin, jnp.arange(t.shape[1]))  # noqa: E731
    return dot_product_attention(turn(q), turn(k), v, causal=causal, scale=scale)


DEPARTURES = {
    "embedding_multiplier_dropped": dict(cfg=dict(embedding_multiplier=1.0)),
    "residual_multiplier_dropped": dict(cfg=dict(residual_multiplier=1.0)),
    "logits_scaling_dropped": dict(cfg=dict(logits_scaling=1.0)),
    # the default scale, the head size's root, for the model's own
    "attention_multiplier_dropped": dict(cfg=dict(attention_multiplier=16 ** -0.5)),
    "rotary_embedding_added": dict(attention_fn=_with_rotary),
}


@pytest.mark.parametrize("name", list(DEPARTURES))
def test_a_departure_in_the_program_fails_the_comparison(name, reference):
    bent = DEPARTURES[name]
    loss, grads, _ = _program(dataclasses.replace(CFG, **bent.get("cfg", {})),
                              bent.get("attention_fn"))
    want_loss, want = reference
    assert _worst_gap(grads, want) > 1e-2, name
    assert abs(loss - want_loss) / want_loss > 3e-6, name   # agreement: 1e-6


def test_norming_before_the_gate_fails_the_comparison(reference, monkeypatch):
    """``hybrid.GatedRMSNorm``'s order, which this model does not have."""
    monkeypatch.setattr(
        granite4_h, "gated_norm",
        lambda num, y, z, w, eps: granite4_h._norm(num, y, w, eps) * jax.nn.silu(z))
    with jax.default_matmul_precision("highest"):
        bent_loss, bent = jax.value_and_grad(lambda p: granite4_h.loss(
            MODEL, {}, p, {"tokens": TOKENS}, Numerics()))(PARAMS)
    want_loss, want = reference
    assert _worst_gap(weights.flatten(bent), want) > 1e-2
    assert abs(float(bent_loss) - want_loss) / want_loss > 3e-6


def test_the_tied_heads_gradient_reaches_the_embedding_once():
    """No ``lm_head`` leaf; the embedding's gradient is the lookup's plus the
    head's, each counted once."""
    model = SSMDecoder(CFG)
    assert "lm_head" not in PARAMS and sum(p.endswith("embedding") for p in SPEC) == 1

    def loss(looked_up, head):
        params = {**PARAMS, "embed_tokens": {"embedding": looked_up}}
        hidden, _ = model.apply({"params": params}, TOKENS, return_hidden=True)
        return chunked_causal_lm_loss(hidden / CFG.logits_scaling, head.T, TOKENS,
                                      chunk_size=16)[0]

    table = PARAMS["embed_tokens"]["embedding"]
    g_lookup, g_head = jax.jit(jax.grad(loss, argnums=(0, 1)))(table, table)
    tied = jax.jit(jax.grad(make_loss_fn(model, ce_chunk=16), has_aux=True),
                   static_argnums=3)(PARAMS, {}, {"tokens": TOKENS}, None)[0]
    assert float(jnp.max(jnp.abs(g_lookup))) > 0 < float(jnp.max(jnp.abs(g_head)))
    np.testing.assert_allclose(tied["embed_tokens"]["embedding"], g_lookup + g_head,
                               rtol=1e-5, atol=1e-7)
    # and the logits the model returns are the tied, scaled ones
    logits, _ = model.apply({"params": PARAMS}, TOKENS)
    hidden, _ = model.apply({"params": PARAMS}, TOKENS, return_hidden=True)
    np.testing.assert_allclose(logits, hidden @ table.T / 8, rtol=1e-5, atol=1e-6)


def test_layer_types_are_laid_out_as_the_runs_of_their_period():
    full = SSMConfig()
    assert len(full.layer_types) == 40 and full.period == PERIOD
    assert full.layer_types.index("attention") == 5
    assert full.runs == (("mamba", 5), ("attention", 1), ("mamba", 4))
    assert full.layer_plan().periods == 4 and CFG.layer_plan().periods == 1
    assert full.head_dim == 64 and full.attention_multiplier == 1 / 64
    odd = dataclasses.replace(CFG, layer_types=("mamba", "attention", "attention"))
    assert odd.runs == (("mamba", 1), ("attention", 2))
    for bad in (("attention",) * 2, ("mamba", "conv"), ()):
        with pytest.raises(ValueError, match="layer_types"):
            dataclasses.replace(CFG, layer_types=bad)
    assert PARAMS["periods"]["run0_mamba"]["mixer"]["A_log"].shape == (1, 5, 8)
    assert PARAMS["periods"]["run1_attention"]["mixer"]["q_proj"]["kernel"].shape \
        == (1, 64, 64)
    assert PARAMS["periods"]["run2_mamba"]["mixer"]["conv"]["kernel"].shape \
        == (1, 4, 4, 160)
    # the model's own initialiser gives the same tree
    made = SSMDecoder(CFG).init(jax.random.key(0), TOKENS)["params"]
    assert {p: v.shape for p, v in weights.flatten(made).items()} \
        == {p: tuple(s) for p, (s, _, _) in SPEC.items()}
    a_log = made["periods"]["run0_mamba"]["mixer"]["A_log"]
    assert float(a_log.min()) >= 0.0 and float(a_log.max()) <= np.log(16.0)


def _trainer(mesh):
    model = SSMDecoder(CFG)

    def init_fn(rng):
        return model.init(rng, jnp.zeros((2, 24), jnp.int32))["params"], {}

    # Adam: Adafactor leaves a (1,) placeholder for a dimension under 128,
    # which no fsdp rule divides
    return Trainer(mesh, sharding_rules(CFG), make_loss_fn(model, ce_chunk=8),
                   optax.adam(3e-3), init_fn)


def test_it_trains_and_a_mesh_with_fsdp_gives_the_one_chip_losses():
    batch = {"tokens": np.asarray(jax.random.randint(jax.random.key(5), (4, 24), 0, 256))}
    losses = {}
    for name, spec, devices in (("one", MeshSpec.for_devices(1), jax.devices()[:1]),
                                ("fsdp", MeshSpec.for_devices(4, fsdp=2), jax.devices()[:4])):
        mesh = build_mesh(spec, devices)
        trainer = _trainer(mesh)
        state = trainer.init(jax.random.key(0))
        if name == "fsdp":
            kernel = state.params["periods"]["run0_mamba"]["mixer"]["in_proj"]["kernel"]
            assert kernel.sharding.spec == jax.sharding.PartitionSpec(None, None, "fsdp")
        out = []
        for _ in range(3):
            state, m = trainer.step(state, shard_batch(mesh, batch))
            out.append(float(m["loss"]))
        assert np.isfinite(float(m["counters"]["ssm_state_rms"]))
        assert float(m["counters"]["ssm_log_decay_min"]) < 0
        losses[name] = out
    assert losses["one"][-1] < losses["one"][0]
    np.testing.assert_allclose(losses["fsdp"], losses["one"], rtol=2e-5)


@pytest.mark.parametrize("what", ["model", "config", "published"])
def test_serving_and_conversion_refuse_it_by_name(what):
    from tpucfn.models.hf_convert import config_from_hf
    from tpucfn.serve.engine import ServeEngine

    with pytest.raises(NotImplementedError, match="recurrent state"):
        if what == "model":
            ServeEngine(SSMDecoder(CFG), {}, max_batch=1, cache_len=8)
        elif what == "config":
            ServeEngine.from_llama(CFG, {})
        else:
            config_from_hf(types.SimpleNamespace(model_type="granitemoehybrid"))
