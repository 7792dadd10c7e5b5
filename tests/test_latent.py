"""``models/latent.py``: the latent attention mixer against plain attention
over the same projections, the second prediction (targets two ahead, one
embedding and one head for both losses), the router's float32 path against the
reference's on 10,000 tokens, and that every block's attention goes through the
flash kernel at the cell's sequence length."""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpucfn.models.latent import (LatentConfig, LatentDecoder, make_loss_fn,
                                  rope_adjacent_pairs, sharding_rules)
from tpucfn.models.llama import causal_lm_loss, chunked_causal_lm_loss
from tpucfn.models.moe import RoutedExperts

CFG = LatentConfig.tiny()


def _model_and_params(cfg=CFG, seq=32, seed=0):
    model = LatentDecoder(cfg)
    tokens = jax.random.randint(jax.random.key(seed), (2, seq), 0, cfg.vocab_size)
    params = model.init(jax.random.key(seed + 1), tokens)["params"]
    # a bias wide enough to move the chosen sets
    bias = lambda k, a: 0.2 * jax.random.normal(jax.random.key(k), a.shape)  # noqa: E731
    params["layers"]["mlp"]["e_score_correction_bias"] = bias(
        7, params["layers"]["mlp"]["e_score_correction_bias"])
    params["mtp"]["block"]["mlp"]["e_score_correction_bias"] = bias(
        8, params["mtp"]["block"]["mlp"]["e_score_correction_bias"])
    return model, params, tokens


def test_rotary_embedding_turns_adjacent_pairs():
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 8))
    y = rope_adjacent_pairs(x, 100.0)
    assert jnp.allclose(y[:, 0], x[:, 0], atol=1e-6)          # position 0: no turn
    # pair i of position p turns by p * theta^(-2i/d); norms of pairs are kept
    pairs = lambda t: t.reshape(1, 5, 2, 4, 2)  # noqa: E731
    assert jnp.allclose(jnp.linalg.norm(pairs(y), axis=-1),
                        jnp.linalg.norm(pairs(x), axis=-1), atol=1e-5)
    ang = 3 * 100.0 ** (-2 / 8)
    e, o = x[0, 3, 1, 2], x[0, 3, 1, 3]
    assert float(y[0, 3, 1, 2]) == pytest.approx(
        float(e * np.cos(ang) - o * np.sin(ang)), abs=1e-5)
    assert float(y[0, 3, 1, 3]) == pytest.approx(
        float(o * np.cos(ang) + e * np.sin(ang)), abs=1e-5)


def test_the_tree_shares_one_embedding_and_one_head():
    _, params, _ = _model_and_params()
    assert set(params) == {"embed_tokens", "dense_0", "layers", "final_norm",
                           "lm_head", "mtp"}
    assert set(params["mtp"]) == {"hnorm", "enorm", "eh_proj", "block",
                                  "final_norm"}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    names = ["/".join(str(k.key) for k in path) for path, _ in flat]
    assert sum(n.endswith("embedding") for n in names) == 1
    assert sum(n.startswith("lm_head") for n in names) == 1
    assert params["layers"]["mlp"]["e_score_correction_bias"].shape == (2, 8)
    assert params["mtp"]["eh_proj"]["kernel"].shape == (128, 64)
    assert "shared_expert_gate" not in params["layers"]["mlp"]


def test_the_second_prediction_targets_two_ahead_and_leaves_the_last_two_out():
    model, params, tokens = _model_and_params()
    (logits, second), _ = model.apply({"params": params}, tokens)
    (hidden, h2), _ = model.apply({"params": params}, tokens, return_hidden=True)
    head = params["lm_head"]["kernel"]
    lm, _ = chunked_causal_lm_loss(hidden, head, tokens, chunk_size=8)
    mtp, _ = chunked_causal_lm_loss(h2, head, tokens, chunk_size=8, ahead=2)
    assert float(lm) == pytest.approx(float(causal_lm_loss(logits, tokens)[0]), rel=1e-5)
    # by hand: position i of the second logits against token i + 2
    lp = jax.nn.log_softmax(second[:, :-2])
    want = -jnp.mean(jnp.take_along_axis(lp, tokens[:, 2:, None], -1))
    assert float(mtp) == pytest.approx(float(want), rel=1e-5)
    loss, (metrics, _) = make_loss_fn(model, ce_chunk=8)(params, {}, {"tokens": tokens}, None)
    assert float(loss) == pytest.approx(float(lm + CFG.mtp_lambda * mtp), rel=1e-6)
    c = metrics["counters"]
    assert float(c["lm_loss"]) == pytest.approx(float(lm), rel=1e-6)
    assert float(c["mtp_loss"]) == pytest.approx(float(mtp), rel=1e-6)
    assert float(c["moe_dropped"]) == 0.0 and float(c["moe_rows"]) == 2 * 32 * 2
    assert float(c["moe_blocks_run"]) == 1.0     # all held: one block is all
    # the last position's placeholder and the last two tokens' targets reach
    # neither loss: other tokens there, same losses
    moved = tokens.at[:, -1].set((tokens[:, -1] + 1) % CFG.vocab_size)
    (_, h2m), _ = model.apply({"params": params}, moved, return_hidden=True)
    assert jnp.allclose(h2m[:, :-2], h2[:, :-2], atol=1e-6)


def test_both_losses_reach_the_embedding_and_the_head():
    model, params, tokens = _model_and_params()
    head = lambda p: p["lm_head"]["kernel"]  # noqa: E731

    def part(which):
        def f(p):
            (h, h2), _ = model.apply({"params": p}, tokens, return_hidden=True)
            if which == "lm":
                return chunked_causal_lm_loss(h, head(p), tokens, chunk_size=8)[0]
            return chunked_causal_lm_loss(h2, head(p), tokens, chunk_size=8, ahead=2)[0]
        return jax.grad(f)(params)

    g_lm, g_mtp = part("lm"), part("mtp")
    g = jax.grad(lambda p: make_loss_fn(model, ce_chunk=8)(
        p, {}, {"tokens": tokens}, None)[0])(params)
    for leaf in (lambda t: t["embed_tokens"]["embedding"], head):
        assert float(jnp.linalg.norm(leaf(g_lm))) > 0
        assert float(jnp.linalg.norm(leaf(g_mtp))) > 0
        assert jnp.allclose(leaf(g), leaf(g_lm) + CFG.mtp_lambda * leaf(g_mtp),
                            rtol=1e-4, atol=1e-7)
    # the next-token loss does not see the prediction block; the bias enters
    # the choice only, so no gradient reaches it
    assert all(float(jnp.max(jnp.abs(x))) == 0.0 for x in jax.tree.leaves(g_lm["mtp"]))
    assert float(jnp.max(jnp.abs(g["layers"]["mlp"]["e_score_correction_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["layers"]["mlp"]["router"]["kernel"]))) > 0.0


@pytest.mark.parametrize("select", ["biased", "unbiased"])
def test_the_router_picks_the_references_sets_on_ten_thousand_tokens(select):
    """``RoutedExperts``' float32 path (sigmoid, choice by score plus bias,
    weights by the score alone) against ``benchmark/reference``'s ``route`` on
    10,000 tokens at 256 experts, 8 a token: the same sets, token for token."""
    from benchmark.reference import joyai_llm_flash as ref

    t, d, e, k = 10_000, 64, 256, 8
    x = jax.random.normal(jax.random.key(1), (t, d))
    layer = RoutedExperts(e, k, 8, (0, e), dtype=jnp.float32, score="sigmoid",
                          select_bias=True, weight_scale=2.5, shared_gate=False)
    params = layer.init(jax.random.key(2), x[:4])["params"]
    params["router"]["kernel"] = 0.1 * jax.random.normal(jax.random.key(3), (d, e))
    if select == "biased":
        params["e_score_correction_bias"] = 0.05 * jax.random.normal(
            jax.random.key(4), (e,))
    model = {"num_experts_per_tok": k, "routed_scaling_factor": 2.5}
    # the program's sets, read off which experts' down-projection a token met:
    # expert j's output is the constant row j
    params["experts"] = {
        "gate_proj": {"kernel": jnp.zeros((e, d, 8)).at[:, 0, :].set(50.0)},
        "up_proj": {"kernel": jnp.zeros((e, d, 8)).at[:, 0, 0].set(1.0)},
        "down_proj": {"kernel": jnp.zeros((e, 8, d)).at[
            jnp.arange(e), 0, jnp.arange(e) % d].set(1.0 + jnp.arange(e) // d)},
    }
    x1 = x.at[:, 0].set(1.0)
    chosen, w = ref.route(model, x1, params)
    out, stats = layer.apply({"params": params}, x1)
    want = jnp.zeros((t, d)).at[jnp.arange(t)[:, None], chosen % d].add(
        w * (1.0 + chosen // d) * jax.nn.silu(50.0))
    assert float(stats["rows"]) == t * k and float(stats["dropped"]) == 0.0
    assert float(jnp.max(jnp.abs(out - want))) <= 1e-4
    if select == "biased":   # and the bias did move the sets
        plain, _ = ref.route(model, x1, dict(
            params, e_score_correction_bias=jnp.zeros((e,))))
        assert 0.3 < float(jnp.mean(jnp.any(
            jnp.sort(plain, -1) != jnp.sort(chosen, -1), axis=-1)))


def test_every_blocks_attention_goes_through_the_flash_kernel_at_s8192(monkeypatch):
    """At the cell's 8,192 positions on a TPU, the dispatch of
    ``kernels/auto.py`` sends the dense layer's, the scanned sparse layers' and
    the prediction block's attention to ``flash_attention`` with keys of
    ``qk_nope + qk_rope`` and values of ``v_head_dim``; the dense path is never
    taken.  Traced by shape only: the scanned body stands for its 4 layers."""
    import tpucfn.kernels.auto as auto
    import tpucfn.ops.attention as dense

    calls = []

    def flash(q, k, v, *, causal=True, **kw):
        calls.append((q.shape, k.shape, v.shape, causal))
        return jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)

    def refuse(*a, **kw):
        raise AssertionError("dense attention at S 8,192")

    monkeypatch.setattr(auto, "_backend", lambda: "tpu")
    monkeypatch.setattr(sys.modules["tpucfn.kernels.flash_attention"],
                        "flash_attention", flash)
    monkeypatch.setattr(dense, "dot_product_attention", refuse)
    cfg = dataclasses.replace(CFG, n_layers=5, remat=False)
    model = LatentDecoder(cfg)
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    params = jax.eval_shape(lambda t: model.init(jax.random.key(0), t), tokens)["params"]
    calls.clear()
    (h, h2), _ = jax.eval_shape(
        lambda p, t: model.apply({"params": p}, t, return_hidden=True), params, tokens)
    assert h.shape == h2.shape == (2, 8192, 64)
    assert params["layers"]["input_norm"]["scale"].shape == (4, 64)
    # the leading dense layer, the scanned body (4 layers; flax traces it once
    # more for its shapes), the prediction block: one shape, all causal
    assert len(calls) >= 3
    assert set(calls) == {((2, 8192, 4, 24), (2, 8192, 4, 24), (2, 8192, 4, 16), True)}


def test_sharding_rules_divide_every_kernel_over_fsdp():
    _, params, _ = _model_and_params()
    rules = sharding_rules(CFG)
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = "/".join(k.key for k in path)
        spec = rules.spec_for(name, leaf.ndim)      # raises if over-long
        if name.endswith(("kernel", "embedding")):
            assert "fsdp" in spec, name
            assert leaf.shape[spec.index("fsdp")] % 2 == 0, name
        else:   # norms' scales and the selection bias: whole on every chip
            assert "fsdp" not in spec, name
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, first_dense=3)


def _trainer(mesh, tx=None):
    import optax

    from tpucfn.train import Trainer

    net = LatentDecoder(CFG)

    def init_fn(rng):
        return net.init(rng, jnp.zeros((8, 32), jnp.int32))["params"], {}

    return Trainer(mesh, sharding_rules(CFG), make_loss_fn(net, ce_chunk=16),
                   tx or optax.adafactor(1e-2), init_fn)


def test_it_trains_through_the_trainer_and_a_mesh_gives_the_one_chip_losses():
    import optax

    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.parallel.sharding import shard_batch

    one = build_mesh(MeshSpec.for_devices(1), jax.devices()[:1])
    four = build_mesh(MeshSpec.for_devices(4, fsdp=2), jax.devices()[:4])
    tokens = np.random.RandomState(1).randint(0, 256, (8, 32)).astype(np.int32)
    out = []
    for mesh in (one, four):
        # Adam: Adafactor's unfactored placeholders (dims under 128 here)
        # share the kernels' paths and do not divide over fsdp
        trainer = _trainer(mesh, tx=optax.adam(3e-3))
        state = trainer.init(jax.random.key(3))
        if mesh is four:
            k = state.params["layers"]["mixer"]["q_b_proj"]["kernel"]
            assert k.sharding.spec == jax.sharding.PartitionSpec(None, None, "fsdp")
        batch = shard_batch(mesh, {"tokens": tokens})
        run = []
        for _ in range(6):
            state, m = trainer.step(state, batch)
            run.append(float(m["loss"]))
        out.append(run)
    assert out[0][-1] < out[0][0]
    assert set(m["counters"]) == {"moe_rows", "moe_load_max_over_mean",
                                  "moe_dropped", "moe_blocks_run", "lm_loss",
                                  "mtp_loss"}
    assert float(m["counters"]["moe_dropped"]) == 0.0
    np.testing.assert_allclose(out[0], out[1], rtol=2e-5)
