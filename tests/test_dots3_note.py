"""The dots3_note decoder (``models/dots3_note.py``) against the plain reference
``benchmark/reference/dots3_note.py`` on seeded weights at a small size
(float32; hidden 64; the leading dense layer and one period of a sparse-
attention layer and three window layers; 24 keys a query of up to 64, so that
the choice drops keys); the indexer's loss reaches its leaves alone; the
shares of heads and experts add up to the whole layer; the choice's kernels
against its ``jnp`` form, ties included; the refusal by name."""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import families, run, weights
from benchmark.reference import dots3_note as reference
from benchmark.reference.joyai_llm_flash import sparse_ffn
from benchmark.reference.numerics import Numerics
from tpucfn.kernels import dsa as kernels
from tpucfn.kernels.flash_attention import pack, unpack
from tpucfn.kernels.auto import auto_attention_static_zero
from tpucfn.models.dots3_note import DenseLayer, Dots3Decoder, make_loss_fn
from tpucfn.ops import dsa

CELL = "dots3-dsa-s16384"
# the cell's rehearsal sizes under their published names; weights drawn wide
# enough that every norm, gate and the indexer move the loss
_C = run.load_cell(CELL, rehearse=True)["config"]
MODEL = {**_C["model"], "initializer_range": 0.1}
JOB = _C["job"]
SPEC = reference.param_spec(MODEL)
INDEXER = "indexer/"


# made once a module, and not at import: each test worker imports every file
@pytest.fixture(scope="module")
def params():
    """Seeded weights by the spec's means and spreads, drawn on the host (the
    device's generator compiles a program a leaf shape)."""
    rng = np.random.default_rng(11)
    return weights.unflatten({
        p: jnp.asarray(mean + std * rng.standard_normal(shape), jnp.float32)
        for p, (shape, mean, std) in SPEC.items()})


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(5), (2, 64), 0, 256))


@pytest.fixture(scope="module")
def cfg():
    return families.load("dots3_note").config_of(MODEL, JOB)


def _program(cfg, params, tokens):
    loss_fn = make_loss_fn(Dots3Decoder(cfg), ce_chunk=16)
    with jax.default_matmul_precision("highest"):
        (loss, (metrics, _)), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True), static_argnums=3)(
                params, {}, {"tokens": tokens}, None)
    return float(loss), grads, metrics


@pytest.fixture(scope="module")
def reference_run(params, tokens):
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: reference.loss(
            MODEL, JOB, p, {"tokens": tokens}, Numerics())))(params)
    return float(loss), grads


@jax.jit
def _gaps(got, want):
    return {p: jnp.max(jnp.abs(got[p] - want[p])) / (jnp.max(jnp.abs(want[p]))
                                                      + 1e-12) for p in got}


def _flat_gap(got, want):
    got, want = weights.flatten(got), weights.flatten(want)
    assert set(got) == set(want) == set(SPEC)
    return {p: float(g) for p, g in _gaps(got, want).items()}


def test_the_model_matches_the_reference_loss_every_gradient_and_an_update(
        cfg, params, tokens, reference_run):
    """The selection drops keys (24 of up to 64), so attention, the gates,
    the window and the indexer's loss all enter; float32 both sides."""
    loss, grads, metrics = _program(cfg, params, tokens)
    want_loss, want = reference_run
    assert loss == pytest.approx(want_loss, rel=1e-6)
    # a leaf's largest gap over its largest entry: float32 sums in two orders
    # (the scan over the head's chunks, blocks of queries in the reference)
    gaps = _flat_gap(grads, want)
    assert max(gaps.values()) < 2e-4, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    # every indexer leaf learns (from the indexer's loss alone, below)
    flat = weights.flatten(grads)
    assert all(float(jnp.max(jnp.abs(flat[p]))) > 0 for p in SPEC if INDEXER in p)
    c = metrics["counters"]
    share = float(c["dsa_select_share"])
    assert 0.0 < share < 1.0 and float(c["dsa_kl"]) > 0.0
    # the share the formula gives: Σ_t min(24, t + 1) over Σ_t (t + 1)
    k, s = MODEL["index_topk"], tokens.shape[1]
    want_share = (k * (k + 1) / 2 + (s - k) * k) / (s * (s + 1) / 2)
    assert share == pytest.approx(want_share, rel=1e-6)
    # one Adafactor update from each side's gradients moves every leaf alike
    tx = optax.adafactor(1e-3)
    moved = jax.jit(lambda g: tx.update(g, tx.init(params), params)[0])
    moved = _flat_gap(*[moved(g) for g in (grads, want)])
    assert max(moved.values()) < 2e-3


def test_the_indexer_learns_from_its_loss_alone(cfg, params):
    """Its inputs are detached and its choice is discrete: in a sparse-
    attention layer the output reaches no indexer leaf, the KL loss no other
    leaf."""
    layer = DenseLayer(cfg, auto_attention_static_zero)
    params = params["dense_0"]
    x = jax.random.normal(jax.random.key(1), (2, 64, MODEL["hidden_size"]))

    def parts(params):
        y, c = layer.apply({"params": params}, x)
        return jnp.stack([jnp.sum(y * y), c["dsa_kl"]])

    both = jax.jit(jax.jacrev(parts))(params)
    for which in (0, 1):
        for p, g in weights.flatten(jax.tree.map(lambda a: a[which], both)).items():
            moved = float(jnp.max(jnp.abs(g))) > 0
            if p.startswith(INDEXER):
                assert moved == bool(which), p
            elif which:
                assert not moved, p


def test_the_shares_of_heads_and_experts_add_up_to_the_whole_layer(params):
    """The guide's share test: a layer's heads in groups of one head (the
    indexer, the down-projections and norms whole on every chip) and its
    experts in two shares (the shared expert and router on every chip,
    counted once): the partial outputs add up to the uncut reference
    layer's."""
    num, m = Numerics(), MODEL
    x = jax.random.normal(jax.random.key(9), (1, 64, m["hidden_size"]))
    for kind, p in (("full", params["dense_0"]),
                    ("window", jax.tree.map(lambda a: a[0],
                                            params["layers"]["window_0"]))):
        heads = reference.widths(m, kind)["heads"]

        @functools.partial(jax.jit, static_argnums=1)
        def attn(p, h, kind=kind):
            mm = dict(m, **{("num_attention_heads" if kind == "full" else
                             "swa_num_attention_heads"): h})
            q, k, v, c_q = reference._latent(mm, kind, num, x, p["mixer"])
            if kind == "full":
                qi, ki, w = reference.indexer(mm, num, x, c_q, p["indexer"])
                a, _ = reference._sparse_attention(mm, num, q, k, v, qi, ki, w, None)
            else:
                a = reference._window_attention(mm, num, q, k, v)
            return reference._gated_out(num, x, a, p["mixer"])

        wd = reference.widths(m, kind)
        qk, vv = wd["nope"] + wd["rot"], wd["nope"] + wd["v"]
        whole = attn(p, heads)
        parts = 0.0
        for g in range(heads):
            sl = slice(g, g + 1)
            mix = dict(p["mixer"])
            mix["q_b_proj"] = {"kernel": p["mixer"]["q_b_proj"]["kernel"][
                :, g * qk:(g + 1) * qk]}
            mix["kv_b_proj"] = {"kernel": p["mixer"]["kv_b_proj"]["kernel"][
                :, g * vv:(g + 1) * vv]}
            mix["gate_proj"] = {"kernel": p["mixer"]["gate_proj"]["kernel"][:, sl]}
            mix["o_proj"] = {"kernel": p["mixer"]["o_proj"]["kernel"][
                g * wd["v"]:(g + 1) * wd["v"]]}
            parts = parts + attn({**p, "mixer": mix}, 1)
        np.testing.assert_allclose(parts, whole, atol=2e-5)
    # experts: the router over all of them, half of those held a share
    mlp = jax.tree.map(lambda a: a[0], params["layers"]["full"])["mlp"]
    ffn = jax.jit(lambda p, first=0: sparse_ffn(m, num, x, p, first),
                  static_argnums=1)
    whole = ffn(mlp)
    shared = {**mlp, "experts": jax.tree.map(lambda a: a[:0], mlp["experts"])}
    parts = -ffn(shared)                                  # counted once
    half = m["n_routed_experts"] // 2
    for first in (0, half):
        held = {**mlp, "experts": jax.tree.map(lambda a: a[first:first + half],
                                               mlp["experts"])}
        parts = parts + ffn(held, first)
    np.testing.assert_allclose(parts, whole, atol=2e-5)


def _indexer_inputs(s=256, hi=4, di=32, seed=0):
    r = jax.random.key(seed)
    return (jax.random.normal(jax.random.fold_in(r, 0), (1, hi, s, di)),
            jax.random.normal(jax.random.fold_in(r, 1), (1, s, di)),
            jax.random.normal(jax.random.fold_in(r, 2), (1, s, hi)))


def test_the_choice_kernel_matches_top_k_and_breaks_ties_to_the_lower_key():
    """``dsa_select`` against ``lax.top_k`` over the ``jnp`` scores (40 keys
    of up to 256, blocks of 128), its log-sum-exp, and rows of equal scores
    (every weight 0): the lowest positions, as ``lax.top_k`` orders ties."""
    qi, ki, w = _indexer_inputs()
    by_jnp = jax.jit(functools.partial(dsa.select, topk=40))
    by_kernel = jax.jit(functools.partial(
        kernels.select, topk=40, block_q=128, block_k=128, interpret=True))
    words, lse = by_jnp(qi, ki, w)
    got, got_lse = by_kernel(qi, ki, w)
    np.testing.assert_array_equal(unpack(got, 256), unpack(words, 256))
    np.testing.assert_allclose(got_lse, lse, rtol=1e-6)
    chosen = unpack(words, 256)[0]
    assert chosen.sum(-1).tolist() == [min(40, t + 1) for t in range(256)]
    tied = jnp.zeros_like(w)
    pos = jnp.arange(256)
    want = (pos[None] < 40) & (pos[:, None] >= pos[None])
    for form in (by_kernel(qi, ki, tied)[0], by_jnp(qi, ki, tied)[0]):
        np.testing.assert_array_equal(unpack(form, 256)[0], want)


def test_the_kl_kernels_match_the_jnp_loss_and_its_gradients():
    """``dsa_kl_fwd`` / ``dsa_kl_dq`` / ``dsa_kl_dk`` on 2 x 2 tiles against
    the ``jnp`` form's value and its autodiff gradients, the attention's
    probabilities read from its log-sum-exp."""
    from tpucfn.ops.attention import dot_product_attention_with_lse

    qi, ki, w = _indexer_inputs()
    words, lse_i = dsa.select(qi, ki, w, topk=40)
    r = jax.random.key(4)
    q, k = (jax.random.normal(jax.random.fold_in(r, i), (1, 256, 2, 48))
            for i in (0, 1))
    v = jax.random.normal(jax.random.fold_in(r, 2), (1, 256, 2, 16))
    _, lse = dot_product_attention_with_lse(
        q, k, v, mask=unpack(words, 256)[:, None])
    scale = 48 ** -0.5

    def by_jnp(qi, ki, w):
        return dsa.kl(qi, ki, w, words, lse_i, q, k, lse, scale=scale)

    def by_kernels(qi, ki, w):
        return dsa.kl_kernels(qi, ki, w, words, lse_i, jnp.swapaxes(q, 1, 2),
                              jnp.swapaxes(k, 1, 2), lse, scale, 128, True)

    want, want_g = jax.jit(jax.value_and_grad(by_jnp, (0, 1, 2)))(qi, ki, w)
    got, got_g = jax.jit(jax.value_and_grad(by_kernels, (0, 1, 2)))(qi, ki, w)
    assert float(got) == pytest.approx(float(want), rel=1e-5) and float(want) > 0
    # float32 sums over tiles in another order than the jnp form's
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_attention_over_every_causal_key_is_causal_attention():
    """A choice of at least S keys is every causal key, and attention over it
    is plain causal attention, through the dense path and the kernels."""
    from tpucfn.kernels.auto import auto_attention_static_zero
    from tpucfn.kernels.flash_attention import flash_attention

    qi, ki, w = _indexer_inputs()
    words, _ = dsa.select(qi, ki, w, topk=256)
    pos = jnp.arange(256)
    np.testing.assert_array_equal(unpack(words, 256)[0], pos[:, None] >= pos[None])
    r = jax.random.key(8)
    q, k = (jax.random.normal(jax.random.fold_in(r, i), (1, 256, 2, 48))
            for i in (0, 1))
    v = jax.random.normal(jax.random.fold_in(r, 2), (1, 256, 2, 16))
    np.testing.assert_allclose(
        auto_attention_static_zero(q, k, v, select=words),
        auto_attention_static_zero(q, k, v), atol=1e-6)
    np.testing.assert_allclose(
        flash_attention(q, k, v, select=words, block_q=128, block_k=128,
                        interpret=True),
        flash_attention(q, k, v, block_q=128, block_k=128, interpret=True),
        atol=1e-6)


def test_the_words_pack_a_mask_and_the_reference_reads_them():
    m = jax.random.uniform(jax.random.key(2), (2, 40, 300)) < 0.3
    words = pack(m)
    assert words.shape == (2, 1, 40, 128)
    np.testing.assert_array_equal(unpack(words, 300), m)
    np.testing.assert_array_equal(reference.unpack_rows(words, 8, 16, 300),
                                  m[:, 8:24])


@pytest.mark.parametrize("what", ["model", "config", "published"])
def test_serving_and_conversion_refuse_it_by_name(what, cfg):
    from tpucfn.models.hf_convert import config_from_hf
    from tpucfn.serve.engine import ServeEngine

    with pytest.raises(NotImplementedError, match="indexer.*dots3_note"):
        if what == "model":
            ServeEngine(Dots3Decoder(cfg), {}, max_batch=1, cache_len=8)
        elif what == "config":
            ServeEngine.from_llama(cfg, {})
        else:
            config_from_hf(types.SimpleNamespace(model_type="dots3_note"))


def test_the_layer_kinds_must_be_whole_periods(cfg):
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(cfg, layer_types=("full", "full", "window", "full"))
    assert cfg.period() == 4 and cfg.layer_plan().periods == 1
