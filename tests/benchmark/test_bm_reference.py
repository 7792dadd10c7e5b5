"""The plain references against the program's models: loss and gradients on
the benchmark's seeded weights, at small sizes, float32, ``highest``."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import weights
from benchmark.reference import decoder, resnet
from benchmark.reference.numerics import Numerics

RN = {"stage_sizes": [1, 2, 1, 1], "width": 8, "num_classes": 10, "image_size": 64}
LM = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
      "vocab_size": 256, "max_position_embeddings": 512, "rope_theta": 1e6,
      "rms_norm_eps": 1e-5, "initializer_range": 0.02}


def _shapes(tree):
    return weights.flatten(jax.tree.map(lambda x: tuple(x.shape), tree))


def test_resnet_reference_matches_the_program():
    """Against the program in float64: in float32 the program's one-pass
    batch variance loses digits (leaf norms off by up to 5e-3 here) that the
    reference's two-pass variance keeps."""
    from tpucfn.models import ResNet, ResNetConfig

    spec, sspec = resnet.param_spec(RN), resnet.state_spec(RN)
    key = weights.seed_key(5)
    params, state = weights.make(spec, key), weights.make(sspec, key)
    rs = np.random.RandomState(0)
    batch = {"image": (rs.randn(16, 64, 64, 3) * 0.7).astype(np.float32),
             "label": rs.randint(0, 10, 16).astype(np.int32)}
    lr, gr = jax.value_and_grad(lambda p: resnet.loss(
        RN, {"label_smoothing": 0.1}, p, batch, Numerics()))(params)

    with jax.enable_x64(True):
        net = ResNet(ResNetConfig(stage_sizes=(1, 2, 1, 1), num_classes=10,
                                  width=8, dtype=jnp.float64,
                                  param_dtype=jnp.float64))
        abstract = jax.eval_shape(lambda: net.init(
            jax.random.key(0), jnp.zeros((1, 64, 64, 3)), train=True))
        assert _shapes(abstract["params"]) == {
            k: tuple(v[0]) for k, v in spec.items()}
        assert _shapes({"batch_stats": abstract["batch_stats"]}) == {
            k: tuple(v[0]) for k, v in sspec.items()}
        to64 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float64), t)  # noqa: E731
        state64 = to64(state)

        def program_loss(p):
            logits, _ = net.apply({"params": p, **state64}, batch["image"],
                                  train=True, mutable=["batch_stats"])
            labels = optax.smooth_labels(
                jax.nn.one_hot(batch["label"], 10), 0.1)
            return optax.softmax_cross_entropy(logits, labels).mean()

        lp, gp = jax.value_and_grad(program_loss)(to64(params))
        lp, gp = float(lp), jax.tree.map(np.asarray, gp)
    assert lp == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        assert float(np.max(np.abs(np.asarray(a) - b))) <= 1e-3 * float(np.max(np.abs(b)))


def test_decoder_reference_matches_the_program():
    from tpucfn.models.llama import Llama, LlamaConfig, chunked_causal_lm_loss

    net = Llama(LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, ffn_dim=128, max_seq=512,
                            rope_theta=1e6, norm_eps=1e-5, dtype=jnp.float32))
    abstract = jax.eval_shape(lambda: net.init(
        jax.random.key(0), jnp.zeros((1, 32), jnp.int32)))
    spec = decoder.param_spec(LM)
    assert _shapes(abstract["params"]) == {k: tuple(v[0]) for k, v in spec.items()}
    params = weights.make(spec, weights.seed_key(7))
    tokens = np.random.RandomState(0).randint(0, 256, (3, 48)).astype(np.int32)

    def program_loss(p):
        h = net.apply({"params": p}, tokens, return_hidden=True)
        return chunked_causal_lm_loss(h, p["lm_head"]["kernel"], tokens,
                                      chunk_size=16)[0]

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program_loss)(params)
        lr, gr = jax.value_and_grad(lambda p: decoder.loss(
            LM, {}, p, {"tokens": tokens}, Numerics()))(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b)))


def test_seeded_weights_repeat_and_differ_by_seed():
    spec = decoder.param_spec(LM)
    big, small = weights.seed_key(2 ** 31 + 11), weights.seed_key(12)
    a, b, c = weights.make(spec, big), weights.make(spec, big), \
        weights.make(spec, small)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))
    assert not bool(jnp.array_equal(la[0], lc[0]))
    leaf = weights.make_leaf(spec, small, "lm_head/kernel")
    assert bool(jnp.array_equal(leaf, c["lm_head"]["kernel"]))
