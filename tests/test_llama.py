import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpucfn.models.llama import (
    Llama,
    LlamaConfig,
    causal_lm_loss,
    sharding_rules,
)
from tpucfn.parallel import ShardingRules, shard_batch
from tpucfn.train import Trainer


def _tokens(b=4, s=32, vocab=256, seed=0):
    rs = np.random.RandomState(seed)
    return rs.randint(0, vocab, (b, s)).astype(np.int32)


def test_forward_shape_dtype():
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    toks = jnp.asarray(_tokens())
    params = model.init(jax.random.key(0), toks)["params"]
    logits = model.apply({"params": params}, toks)
    assert logits.shape == (4, 32, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_causality():
    """Changing future tokens must not change past logits."""
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    toks = jnp.asarray(_tokens(b=1))
    params = model.init(jax.random.key(0), toks)["params"]
    base = model.apply({"params": params}, toks)
    toks2 = toks.at[0, 20:].set((toks[0, 20:] + 7) % cfg.vocab_size)
    pert = model.apply({"params": params}, toks2)
    np.testing.assert_allclose(
        np.asarray(base[0, :19]), np.asarray(pert[0, :19]), atol=1e-5
    )
    assert np.abs(np.asarray(base[0, 20:]) - np.asarray(pert[0, 20:])).max() > 1e-3


def test_scan_matches_unrolled():
    cfg = LlamaConfig.tiny()
    cfg_unroll = dataclasses.replace(cfg, scan_layers=False)
    toks = jnp.asarray(_tokens(b=2, s=16))
    scanned = Llama(cfg)
    unrolled = Llama(cfg_unroll)
    p_scan = scanned.init(jax.random.key(0), toks)["params"]
    # restack scanned params into the unrolled tree
    p_unroll = unrolled.init(jax.random.key(0), toks)["params"]
    for i in range(cfg.n_layers):
        p_unroll[f"layers_{i}"] = jax.tree.map(lambda x: x[i], p_scan["layers"])
    for k in ("embed_tokens", "final_norm", "lm_head"):
        p_unroll[k] = p_scan[k]
    out_s = scanned.apply({"params": p_scan}, toks)
    out_u = unrolled.apply({"params": p_unroll}, toks)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_u), atol=1e-5)


def test_remat_modes_numerics_identical():
    """remat is a flops/HBM schedule choice, never a numerics one: every
    policy (full, dots, dots_no_batch, none) must produce the same loss
    and grads bit-for-bit on CPU."""
    from tpucfn.models.llama import causal_lm_loss

    toks = jnp.asarray(_tokens(b=2, s=16))
    base = LlamaConfig.tiny()
    params = Llama(base).init(jax.random.key(0), toks)["params"]

    def lg(remat):
        cfg = dataclasses.replace(base, remat=remat)
        model = Llama(cfg)

        def loss(p):
            return causal_lm_loss(model.apply({"params": p}, toks), toks)[0]

        return jax.jit(jax.value_and_grad(loss))(params)

    l_ref, g_ref = lg(True)
    for mode in ("dots", "dots_no_batch", False):
        l_m, g_m = lg(mode)
        np.testing.assert_allclose(float(l_m), float(l_ref), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g_m), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    with pytest.raises(ValueError, match="remat="):
        dataclasses.replace(base, remat="bogus")


def test_llama3_8b_param_count():
    cfg = LlamaConfig.llama3_8b()
    model = Llama(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), toks))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(shapes["params"]))
    assert 8.0e9 < n < 8.1e9  # Llama-3 8B ≈ 8.03B params


def _llama_trainer(mesh, rules, cfg):
    model = Llama(cfg)
    sample = jnp.zeros((1, 8), jnp.int32)

    def init_fn(rng):
        return model.init(rng, sample)["params"], {}

    def loss_fn(params, mstate, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"])
        loss, acc = causal_lm_loss(logits, batch["tokens"])
        return loss, ({"accuracy": acc}, mstate)

    return Trainer(mesh, rules, loss_fn, optax.adamw(3e-3), init_fn)


def test_tp_fsdp_training_matches_replicated(mesh8):
    """TP×FSDP sharded training must be numerically identical to fully
    replicated training — placement, not math (SURVEY.md §2.3)."""
    cfg = LlamaConfig.tiny()
    batch = {"tokens": _tokens(b=8, s=16)}
    results = {}
    for name, rules in [
        ("replicated", ShardingRules(((r".*", P()),))),
        ("tp_fsdp", sharding_rules(cfg)),
    ]:
        trainer = _llama_trainer(mesh8, rules, cfg)
        state = trainer.init(jax.random.key(0))
        b = shard_batch(mesh8, batch)
        for _ in range(3):
            state, m = trainer.step(state, b)
        results[name] = float(m["loss"])
    np.testing.assert_allclose(results["replicated"], results["tp_fsdp"], rtol=2e-4)


def test_tp_fsdp_params_actually_sharded(mesh8):
    cfg = LlamaConfig.tiny()
    trainer = _llama_trainer(mesh8, sharding_rules(cfg), cfg)
    state = trainer.init(jax.random.key(0))
    qk = state.params["layers"]["attn"]["q_proj"]["kernel"]
    assert qk.sharding.spec == P(None, "fsdp", "tensor")
    # global (2, 64, 64) → per-device (2, 32, 32) on fsdp=2 × tensor=2
    assert qk.addressable_shards[0].data.shape == (2, 32, 32)


def test_training_learns(mesh_dp8):
    cfg = LlamaConfig.tiny()
    trainer = _llama_trainer(mesh_dp8, sharding_rules(cfg, tensor=False), cfg)
    state = trainer.init(jax.random.key(0))
    batch = shard_batch(mesh_dp8, {"tokens": _tokens(b=8, s=32)})
    first = None
    for _ in range(30):
        state, m = trainer.step(state, batch)
        first = first if first is not None else float(m["loss"])
    assert float(m["loss"]) < first * 0.7  # memorizing one batch


def test_z_loss_penalizes_large_logits():
    logits = jnp.ones((1, 8, 16)) * 10
    toks = jnp.zeros((1, 8), jnp.int32)
    l0, _ = causal_lm_loss(logits, toks)
    l1, _ = causal_lm_loss(logits, toks, z_loss=1e-2)
    assert float(l1) > float(l0)


# ---- chunked CE: the no-materialized-logits loss path --------------------


def test_chunked_causal_lm_loss_matches_dense():
    """Values, accuracy, and grads (all params) equal the materialized-
    logits path, across chunk sizes incl. non-dividing ones and z-loss."""
    from tpucfn.models.llama import chunked_causal_lm_loss

    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    toks = jnp.asarray(_tokens(b=2, s=33))
    params = model.init(jax.random.key(0), toks)["params"]

    def dense_loss(p, z=0.0):
        return causal_lm_loss(model.apply({"params": p}, toks), toks, z_loss=z)

    def chunked_loss(p, chunk, z=0.0):
        h = model.apply({"params": p}, toks, return_hidden=True)
        return chunked_causal_lm_loss(h, p["lm_head"]["kernel"], toks,
                                      chunk_size=chunk, z_loss=z)

    l_ref, acc_ref = jax.jit(dense_loss)(params)
    for chunk in (5, 8, 32, 512):  # 32 tokens: non-dividing, dividing, > n
        l, acc = jax.jit(lambda p: chunked_loss(p, chunk))(params)
        np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-6)
        np.testing.assert_allclose(float(acc), float(acc_ref), rtol=1e-6)

    lz_ref, _ = jax.jit(lambda p: dense_loss(p, 1e-3))(params)
    lz, _ = jax.jit(lambda p: chunked_loss(p, 8, 1e-3))(params)
    np.testing.assert_allclose(float(lz), float(lz_ref), rtol=1e-6)

    g_ref = jax.jit(jax.grad(lambda p: dense_loss(p)[0]))(params)
    g = jax.jit(jax.grad(lambda p: chunked_loss(p, 8)[0]))(params)
    flat_ref = jax.tree.leaves_with_path(g_ref)
    flat = dict(jax.tree.leaves_with_path(g))
    for path, leaf_ref in flat_ref:
        np.testing.assert_allclose(np.asarray(flat[path]),
                                   np.asarray(leaf_ref),
                                   atol=1e-6, err_msg=str(path))


def _dense_head(hidden, w, tokens, ahead=1, z_loss=0.0):
    """The head over materialized fp32 logits: mean over B × (S - ahead),
    a -1 target adding nothing to the sum."""
    logits = hidden[:, :-ahead] @ w
    t = tokens[:, ahead:]
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(t, 0))
    if z_loss:
        per = per + z_loss * jax.nn.logsumexp(logits, -1) ** 2
    valid = t >= 0
    acc = jnp.sum(valid & (jnp.argmax(logits, -1) == t)) / t.size
    return jnp.sum(jnp.where(valid, per, 0.0)) / t.size, acc


@pytest.mark.parametrize("order, ahead, z_loss, tied, padded", [
    ("vocab", 1, 0.0, False, False),
    ("token", 1, 0.0, False, False),
    ("vocab", 2, 0.0, False, True),
    ("token", 2, 0.0, True, True),
    ("vocab", 1, 1e-3, True, False),
    ("token", 1, 1e-3, False, True),
    ("vocab", 2, 1e-3, True, True),
])
def test_the_heads_backward_matches_dense_in_either_order(
        monkeypatch, order, ahead, z_loss, tied, padded):
    """Loss, accuracy and the gradients of the hidden states and of the
    table equal the materialized logits' in fp32, the backward walking
    blocks of 128 columns over a vocabulary of 300 (a narrower last block)
    or the forward's chunks; B 2, 37 positions with a target in chunks of
    8, so the last chunk is padded."""
    from tpucfn.models import llama

    monkeypatch.setattr(llama, "vocab_block",
                        lambda *a, **k: 128 if order == "vocab" else 0)
    b, s, d, v = 2, 37 + ahead, 16, 300
    kh, kw = jax.random.split(jax.random.key(3))
    hidden = jax.random.normal(kh, (b, s, d), jnp.float32)
    table = jax.random.normal(kw, (v, d) if tied else (d, v)) * 0.5
    tokens = jnp.asarray(_tokens(b=b, s=s, vocab=v, seed=4))
    if padded:
        tokens = tokens.at[:, -5:].set(-1).at[0, 7].set(-1)
    as_w = (lambda t: t.T) if tied else (lambda t: t)

    def loss(fn, h, t):
        return fn(h, as_w(t))

    def chunked(h, w):
        return llama.chunked_causal_lm_loss(h, w, tokens, chunk_size=8,
                                            z_loss=z_loss, ahead=ahead)

    def dense(h, w):
        return _dense_head(h, w, tokens, ahead=ahead, z_loss=z_loss)

    (l, acc), (l_ref, acc_ref) = (jax.jit(lambda h, t, f=f: loss(f, h, t))(
        hidden, table) for f in (chunked, dense))
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-6)
    np.testing.assert_allclose(float(acc), float(acc_ref), rtol=1e-6)
    if not padded and ahead == 1 and not z_loss:
        l_lm, acc_lm = causal_lm_loss(hidden @ as_w(table), tokens)
        np.testing.assert_allclose(float(l), float(l_lm), rtol=1e-6)
        np.testing.assert_allclose(float(acc), float(acc_lm), rtol=1e-6)

    grads, grads_ref = (jax.jit(jax.grad(
        lambda h, t, f=f: loss(f, h, t)[0], argnums=(0, 1)))(hidden, table)
        for f in (chunked, dense))
    for got, want, name in zip(grads, grads_ref, ("hidden", "table")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7, err_msg=name)


@pytest.mark.parametrize("width", [128, 0])
def test_the_heads_backward_holds_with_the_table_sharded(
        monkeypatch, mesh8, width):
    """Under GSPMD, with the table split over the vocabulary as
    ``sharding_rules`` splits ``lm_head/kernel`` (blocks of 128 cross the
    shards' edges at 150) and the batch over ``data``, loss and gradients
    equal the unsharded materialized logits'."""
    from jax.sharding import NamedSharding

    from tpucfn.models import llama

    monkeypatch.setattr(llama, "vocab_block", lambda *a, **k: width)
    b, s, d, v = 2, 41, 16, 300
    kh, kw = jax.random.split(jax.random.key(5))
    hidden = jax.random.normal(kh, (b, s, d), jnp.float32)
    w = jax.random.normal(kw, (d, v)) * 0.5
    tokens = jnp.asarray(_tokens(b=b, s=s, vocab=v, seed=6))
    placed = (jax.device_put(hidden, NamedSharding(mesh8, P("data"))),
              jax.device_put(w, NamedSharding(mesh8, P("fsdp", "tensor"))))

    def chunked(h, w):
        return llama.chunked_causal_lm_loss(h, w, tokens, chunk_size=8,
                                            z_loss=1e-4)[0]

    def dense(h, w):
        return _dense_head(h, w, tokens, z_loss=1e-4)[0]

    (l, g), (l_ref, g_ref) = (
        jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))(*placed),
        jax.jit(jax.value_and_grad(dense, argnums=(0, 1)))(hidden, w))
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-6)
    for got, want, name in zip(g, g_ref, ("hidden", "table")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7, err_msg=name)


@pytest.mark.parametrize("cell, rows, chunks, d, v, order", [
    ("granite4h-ssd-s16384", 16384, 32, 2048, 100352, "vocab"),
    ("mistral7b-s8192", 8192, 16, 4096, 32768, "vocab"),
    ("olmohybrid-gdn-s16384", 16384, 32, 3840, 25088, "vocab"),
    ("qwen3next-ep8-s8192", 16384, 16, 2048, 18992, "vocab"),
    ("joyai-mla-s8192", 16384, 16, 2048, 16160, "vocab"),
    ("mistral7b-s1024", 8192, 2, 4096, 32768, "vocab"),
    # one chunk of many rows against a wide table: each block would read
    # and write dh, where one chunk sums the table gradient once
    ("32 x 512 over 128,256 rows", 16384, 1, 4096, 128256, "token"),
])
def test_the_heads_order_follows_the_bytes(cell, rows, chunks, d, v, order):
    from tpucfn.models.llama import HEAD_BLOCK_BYTES, vocab_block

    width = vocab_block(rows, chunks, d, v)
    assert (width > 0) == (order == "vocab"), cell
    if width:
        assert width % 128 == 0 and width < v, width
        assert rows * width * 4 <= HEAD_BLOCK_BYTES
        blocks = -(-v // width)
        assert (blocks - 1) * width < v <= blocks * width


@pytest.mark.parametrize("order", ["vocab_blocks", "token_chunks"])
def test_the_heads_backward_is_mapped_as_backward(monkeypatch, order):
    """A tiny decoder's compiled step puts the head's backward products under
    pass ``backward`` and its order's scope, none under ``forward`` or
    ``remat``, and leaves no instruction of the head without a pass."""
    from tpucfn.models import llama
    from tpucfn.obs.program import scope_map

    if order == "token_chunks":
        monkeypatch.setattr(llama, "vocab_block", lambda *a, **k: 0)
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    toks = jnp.asarray(_tokens(b=2, s=33))
    params = model.init(jax.random.key(0), toks)["params"]

    def loss(p):
        h = model.apply({"params": p}, toks, return_hidden=True)
        return llama.chunked_causal_lm_loss(h, p["lm_head"]["kernel"], toks,
                                            chunk_size=8)[0]

    text = jax.jit(jax.value_and_grad(loss)).lower(params).compile().as_text()
    classes, ops = scope_map(text)
    used = [classes[i] for i in set(ops.values())]
    head = [c for c in used if c[1] == "lm_head" or
            c[1].startswith("lm_head/")]
    walk = [c for c in head if c[1] == f"lm_head/{order}"]
    assert any(c[3] for c in walk), head              # its products
    assert {c[0] for c in walk} == {"backward"}, walk
    assert not [c for c in head if c[0] in ("remat", "none")], head
    assert {c[1] for c in head if c[0] == "forward"} == {"lm_head"}, head
    assert any(c[3] for c in head if c[0] == "forward"), head
