"""The Olmo-Hybrid decoder (``models/olmo_hybrid.py``): the model against the
plain reference ``benchmark/reference/olmo_hybrid.py`` on seeded weights at a
small size (float32; hidden 64, 4 heads of 16 for attention, 4 key heads of 12
serving 4 value heads of 24, chunk 8, one period of three DeltaNet layers and
the attention layer), each departure from the published equations failing that
comparison, the tree and its rules, the trainer, the refusal by name."""

import dataclasses
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import weights
from benchmark.reference import olmo_hybrid
from benchmark.reference.numerics import Numerics
from tpucfn.mesh import MeshSpec, build_mesh
from tpucfn.models import olmo_hybrid as model_module
from tpucfn.models.layers import apply_rope, rope_frequencies
from tpucfn.models.olmo_hybrid import (OlmoHybridConfig, OlmoHybridDecoder,
                                       make_loss_fn, sharding_rules)
from tpucfn.ops.attention import dot_product_attention
from tpucfn.parallel import shard_batch
from tpucfn.train import Trainer

CFG = OlmoHybridConfig.tiny()
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the same sizes under their published names; weights drawn wide enough that
# every norm and gate moves the loss
MODEL = {
    "hidden_size": 64, "num_hidden_layers": 4, "layer_types": PERIOD,
    "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 128,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "attention_bias": False,
    "vocab_size": 256, "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 12, "linear_value_head_dim": 24,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "initializer_range": 0.1, "a_log_mean": 1.386, "a_log_std": 0.693,
    "dt_bias_mean": -3.0, "dt_bias_std": 1.0}
SPEC = olmo_hybrid.param_spec(MODEL)
PARAMS = weights.make(SPEC, weights.seed_key(7))
TOKENS = np.asarray(jax.random.randint(jax.random.key(3), (2, 37), 0, 256))


def _program(cfg=CFG, attention_fn=None):
    """(loss, flat gradients, metrics) of the program's own loss function."""
    loss_fn = make_loss_fn(OlmoHybridDecoder(cfg, attention_fn=attention_fn),
                           ce_chunk=16)
    with jax.default_matmul_precision("highest"):
        (loss, (metrics, _)), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True), static_argnums=3)(
                PARAMS, {}, {"tokens": TOKENS}, None)
    return float(loss), weights.flatten(grads), metrics


@pytest.fixture(scope="module")
def reference():
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: olmo_hybrid.loss(
            MODEL, {}, p, {"tokens": TOKENS}, Numerics())))(PARAMS)
    return float(loss), weights.flatten(grads)


def _worst_gap(got, want):
    return max(float(jnp.max(jnp.abs(got[p] - want[p])))
               / (float(jnp.max(jnp.abs(want[p]))) + 1e-12) for p in SPEC)


def test_the_model_matches_the_reference_loss_and_every_gradient_leaf(reference):
    """The chunked rule (through the ``jnp`` preparation here) against the
    recurrence position by position, beta over (0, 2); the dispatching
    attention against blocks of queries; the chunked cross-entropy against
    the reference's."""
    loss, grads, metrics = _program()
    want_loss, want = reference
    # float32 both sides; the loss is a mean of 72 positions' terms
    assert loss == pytest.approx(want_loss, rel=1e-6)
    assert set(grads) == set(want) == set(SPEC)
    for path in SPEC:
        assert grads[path].shape == tuple(SPEC[path][0]), path
        assert float(jnp.max(jnp.abs(want[path]))) > 0, path
    # float32 both sides, but a rule that can reflect (beta up to 2) under a
    # post-norm block is sensitive to the order of its sums: against a
    # float64 run of the reference the program read 6.4e-5 and the float32
    # reference itself 9.6e-5, so the two sides are held to twice their sum
    assert _worst_gap(grads, want) < 3e-4
    c = metrics["counters"]
    assert set(c) == {"gdn_reflect_share", "gdn_state_rms"}
    assert 0.0 < float(c["gdn_reflect_share"]) < 1.0
    assert float(c["gdn_state_rms"]) > 0.0


def _with_rotary(q, k, v, *, causal=True, scale=None, **_):
    cos, sin = rope_frequencies(q.shape[-1], q.shape[1], 10000.0)
    turn = lambda t: apply_rope(t, cos, sin, jnp.arange(t.shape[1]))  # noqa: E731
    return dot_product_attention(turn(q), turn(k), v, causal=causal, scale=scale)


class _PreNormLayer(model_module.OlmoHybridLayer):
    """The block most decoders have, ``x + mixer(norm(x))``: not Olmo's."""

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        norm = lambda name: model_module.RMSNorm(  # noqa: E731
            cfg.norm_eps, cfg.dtype, name=name)
        if self.kind == "full":
            mixer = model_module.Attention(cfg, self.attention_fn, name="mixer")
        else:
            mixer = model_module.GatedDeltaNet.of(cfg, beta_scale=2.0, name="mixer")
        mixed, readings = mixer(norm("post_mixer_norm")(x))
        h = x + mixed
        y = model_module.SwiGLUMLP(cfg.ffn_dim, cfg.dtype, cfg.param_dtype,
                                   name="mlp")(norm("post_mlp_norm")(h))
        return h + y, readings


class _HeadwiseRMSNorm(nn.Module):
    """A norm a head, with the whole width's weight (``q_norm/scale``)."""

    heads: int

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        t = x.reshape(x.shape[:-1] + (self.heads, -1)).astype(jnp.float32)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + 1e-6)
        return t.reshape(x.shape) * w


class _HeadwiseQKNorm(model_module.Attention):
    """QK-norm a head, as ``models/hybrid.GatedAttention`` has it: not Olmo's
    one norm over the whole projection."""

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hd = cfg.n_heads, cfg.head_dim
        dense = lambda name: model_module._dense(cfg, h * hd, name)  # noqa: E731
        q = _HeadwiseRMSNorm(h, name="q_norm")(dense("q_proj")(x)).reshape(b, s, h, hd)
        k = _HeadwiseRMSNorm(h, name="k_norm")(dense("k_proj")(x)).reshape(b, s, h, hd)
        v = dense("v_proj")(x).reshape(b, s, h, hd)
        out = self.attention_fn(q, k, v, causal=True)
        return model_module._dense(cfg, x.shape[-1], "o_proj")(
            out.reshape(b, s, h * hd)), {}


DEPARTURES = {
    # beta = sigmoid(b): no reflections
    "negative_eigenvalues_dropped": dict(cfg=dict(allow_neg_eigval=False)),
    "rotary_embedding_added": dict(attention_fn=_with_rotary),
    "pre_norm_block": dict(patch=("OlmoHybridLayer", _PreNormLayer)),
    "qk_norm_a_head": dict(patch=("Attention", _HeadwiseQKNorm)),
}


@pytest.mark.parametrize("name", list(DEPARTURES))
def test_a_departure_in_the_program_fails_the_comparison(name, reference, monkeypatch):
    bent = DEPARTURES[name]
    if "patch" in bent:
        monkeypatch.setattr(model_module, *bent["patch"])
    loss, grads, _ = _program(dataclasses.replace(CFG, **bent.get("cfg", {})),
                              bent.get("attention_fn"))
    want_loss, want = reference
    assert _worst_gap(grads, want) > 1e-2, name
    assert abs(loss - want_loss) / want_loss > 3e-6, name   # agreement: 1e-6


def test_the_tree_is_stacked_by_period_and_the_rules_fit_it():
    made = OlmoHybridDecoder(CFG).init(jax.random.key(0), TOKENS)["params"]
    assert {p: v.shape for p, v in weights.flatten(made).items()} \
        == {p: tuple(s) for p, (s, _, _) in SPEC.items()}
    lin, full = made["periods"]["linear"], made["periods"]["full"]
    assert lin["mixer"]["k_proj"]["kernel"].shape == (1, 3, 64, 4 * 12)
    assert lin["mixer"]["v_proj"]["kernel"].shape == (1, 3, 64, 4 * 24)
    assert full["mixer"]["q_norm"]["scale"].shape == (1, 64)   # the whole width
    assert "lm_head" in made and "input_norm" not in lin
    rules = sharding_rules(CFG)
    flat, _ = jax.tree_util.tree_flatten_with_path(made)
    for path, leaf in flat:
        name = "/".join(k.key for k in path)
        spec = rules.spec_for(name, leaf.ndim)
        if name.endswith("kernel") and "conv" not in name:
            assert "fsdp" in spec, name
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, n_layers=6)
    full_size = OlmoHybridConfig()
    assert full_size.head_dim == 128 and full_size.layer_plan().periods == 8


def test_it_trains_and_a_mesh_with_fsdp_gives_the_one_chip_losses():
    batch = {"tokens": np.asarray(jax.random.randint(jax.random.key(5), (4, 24), 0, 256))}
    losses = {}
    model = OlmoHybridDecoder(CFG)
    for name, spec, devices in (("one", MeshSpec.for_devices(1), jax.devices()[:1]),
                                ("fsdp", MeshSpec.for_devices(4, fsdp=2), jax.devices()[:4])):
        mesh = build_mesh(spec, devices)
        # Adam: Adafactor leaves a (1,) placeholder for a dimension under 128,
        # which no fsdp rule divides
        trainer = Trainer(mesh, sharding_rules(CFG), make_loss_fn(model, ce_chunk=8),
                          optax.adam(3e-3), lambda rng: (model.init(
                              rng, jnp.zeros((2, 24), jnp.int32))["params"], {}))
        state = trainer.init(jax.random.key(0))
        out = []
        for _ in range(3):
            state, m = trainer.step(state, shard_batch(mesh, batch))
            out.append(float(m["loss"]))
        assert 0.0 < float(m["counters"]["gdn_reflect_share"]) < 1.0
        losses[name] = out
    assert losses["one"][-1] < losses["one"][0]
    # the same sums in another order: the first loss agrees to round-off;
    # Adam divides each gradient by its own root, so the 1e-4 that a
    # reflecting rule's gradients carry (the comparison above) moves the
    # parameters whole, and the third loss reads 1.1e-4 apart
    np.testing.assert_allclose(losses["fsdp"][0], losses["one"][0], rtol=1e-6)
    np.testing.assert_allclose(losses["fsdp"], losses["one"], rtol=5e-4)


@pytest.mark.parametrize("what", ["model", "config", "published"])
def test_serving_and_conversion_refuse_it_by_name(what):
    from tpucfn.models.hf_convert import config_from_hf
    from tpucfn.serve.engine import ServeEngine

    with pytest.raises(NotImplementedError, match="recurrent state.*olmo_hybrid"):
        if what == "model":
            ServeEngine(OlmoHybridDecoder(CFG), {}, max_batch=1, cache_len=8)
        elif what == "config":
            ServeEngine.from_llama(CFG, {})
        else:
            config_from_hf(types.SimpleNamespace(model_type="olmo_hybrid"))
