"""One chip's share of JoyAI-LLM-Flash (48B-A2.7B, ``model_type``
``joyai_llm_flash``) as its published configuration describes it: DeepSeek-V3's
layers at this model's widths, forward and loss in plain float32, no kernel, no
sort, nothing of ``tpucfn``.

With ``x`` a token's hidden state and ``N`` the RMS norm ``x * rsqrt(mean(x^2)
+ eps) * w`` (``w`` starts at 1):

- Block: ``h = x + Attn(N(x)); y = h + FFN(N(h))``.
- Latent attention: ``c_q = N(x W_qa)``; ``[q_nope | q_rot] = c_q W_qb`` a
  head; ``[c_kv | k_r] = x W_kva``; ``[k_nope | v] = N(c_kv) W_kvb`` a head;
  ``q = [q_nope | R(q_rot)]``, ``k = [k_nope | R(k_r)]`` with the one rotary key
  shared by every head; ``R`` turns adjacent pairs ``(2i, 2i + 1)`` by
  ``pos * theta^(-2i / rot)`` (``rope_interleave``); causal softmax of
  ``q k^T / sqrt(qk_head_dim)`` over ``v``; the heads' outputs through ``W_o``.
- Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; every later one sparse: ``s = sigmoid(x W_r)`` in
  float32 over all ``router_experts``; the chosen set is the
  ``num_experts_per_tok`` largest of ``s + b`` (``n_group`` 1: no group limit);
  the weights are ``s`` of the chosen over their sum (+ 1e-20) times
  ``routed_scaling_factor``; this chip's share is the sum over the chosen
  experts among the ``n_routed_experts`` held (the first ones), each a SwiGLU
  of ``moe_intermediate_size``, plus the shared expert, whole and ungated.
  ``b`` (``e_score_correction_bias``) enters the choice only.
- After the last block the final norm and the untied head.
- Multi-token prediction (``num_nextn_predict_layers`` 1): with ``h0`` the last
  block's output before the final norm, ``h' = [N(h0_i) | N(Emb(t_(i+1)))]
  W_eh``, one sparse block, a final norm of its own, then the same head and the
  same embedding; it predicts ``t_(i+2)``.  ``loss = CE(next) + mtp_lambda *
  CE(after next)``.

Departures from the published description, all listed in the configuration's
file: the experts' gate and up projections are leaves of their own; the
selection bias stays where the seed put it (its out-of-graph update has no key
in the configuration); ``mtp_lambda`` is assumed; no auxiliary balance loss; the
prediction block runs at all ``S`` positions, the last of which is fed the
embedding of token 0 in place of the token past the end: causality keeps that
position out of every position the loss reads, and the last two positions are
out of the second loss.

The control (``Numerics("fp8")``) rounds the operands of every product and, via
``_held``, every tensor the configuration's compute dtype holds between them,
as ``benchmark/reference/qwen3_next.py`` does; in float32 ``_held`` changes
nothing.

To fit one chip in float32 a block's activations are recomputed in the backward
pass, scores are made a block of queries at a time, experts one at a time and
the head's logits in blocks; none of it changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import LOGIT_BLOCK
from benchmark.reference.numerics import Numerics

QUERY_BLOCK = 256


def _attention_spec(model, lead=()):
    d, std = model["hidden_size"], model["initializer_range"]
    h, rq, rkv = (model["num_attention_heads"], model["q_lora_rank"],
                  model["kv_lora_rank"])
    nope, rot, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return {
        "input_norm/scale": (lead + (d,), 1.0, 0.0),
        "mixer/q_a_proj/kernel": (lead + (d, rq), 0.0, std),
        "mixer/q_a_norm/scale": (lead + (rq,), 1.0, 0.0),
        "mixer/q_b_proj/kernel": (lead + (rq, h * (nope + rot)), 0.0, std),
        "mixer/kv_a_proj/kernel": (lead + (d, rkv + rot), 0.0, std),
        "mixer/kv_a_norm/scale": (lead + (rkv,), 1.0, 0.0),
        "mixer/kv_b_proj/kernel": (lead + (rkv, h * (nope + dv)), 0.0, std),
        "mixer/o_proj/kernel": (lead + (h * dv, d), 0.0, std),
        "post_attn_norm/scale": (lead + (d,), 1.0, 0.0),
    }


def _swiglu_spec(prefix, d, f, std, lead=()):
    return {f"{prefix}/gate_proj/kernel": (lead + (d, f), 0.0, std),
            f"{prefix}/up_proj/kernel": (lead + (d, f), 0.0, std),
            f"{prefix}/down_proj/kernel": (lead + (f, d), 0.0, std)}


def _sparse_spec(model, lead=()):
    d, std = model["hidden_size"], model["initializer_range"]
    f = model["moe_intermediate_size"]
    return {
        **_attention_spec(model, lead),
        "mlp/router/kernel": (lead + (d, model["router_experts"]), 0.0, std),
        "mlp/e_score_correction_bias": (lead + (model["router_experts"],), 0.0,
                                        model["selection_bias_std"]),
        **_swiglu_spec("mlp/experts", d, f, std,
                       lead + (model["n_routed_experts"],)),
        **_swiglu_spec("mlp/shared_expert", d,
                       f * model["n_shared_experts"], std, lead),
    }


def _depths(model):
    dense = model["first_k_dense_replace"]
    return dense, model["num_hidden_layers"] - dense


def param_spec(model) -> dict:
    d, std, v = model["hidden_size"], model["initializer_range"], model["vocab_size"]
    n_dense, n_sparse = _depths(model)
    if model["num_nextn_predict_layers"] != 1 or model["moe_layer_freq"] != 1:
        raise ValueError("one prediction block and every later layer sparse")
    spec = {"embed_tokens/embedding": ((v, d), 0.0, std)}
    for i in range(n_dense):
        spec.update({f"dense_{i}/{k}": s for k, s in {
            **_attention_spec(model),
            **_swiglu_spec("mlp", d, model["intermediate_size"], std)}.items()})
    spec.update({f"layers/{k}": s
                 for k, s in _sparse_spec(model, (n_sparse,)).items()})
    spec.update({
        "final_norm/scale": ((d,), 1.0, 0.0),
        "lm_head/kernel": ((d, v), 0.0, std),
        "mtp/hnorm/scale": ((d,), 1.0, 0.0),
        "mtp/enorm/scale": ((d,), 1.0, 0.0),
        "mtp/eh_proj/kernel": ((2 * d, d), 0.0, std),
        **{f"mtp/block/{k}": s for k, s in _sparse_spec(model).items()},
        "mtp/final_norm/scale": ((d,), 1.0, 0.0),
    })
    return spec


def state_spec(model) -> dict:
    return {}


def _held(num: Numerics, x):
    """A tensor held in the configuration's compute dtype: float32 here, and
    in the control rounded as a product's operand is."""
    return num.operand(x)


def _norm(num: Numerics, x, w, eps):
    return _held(num, x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w)


def _turn(x, theta):
    """Rotary embedding over adjacent pairs; x: (B, S, H, D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * sn, odd * c + even * sn],
                     axis=-1).reshape(x.shape)


def _attention(num: Numerics, q, k, v):
    """Causal softmax attention, a block of queries at a time; q, k: (B, S, H,
    Dqk); v: (B, S, H, Dv) -> (B, S, H * Dv)."""
    b, s, h, d = q.shape
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(b, -1, block, h, d), 1, 0)
    first = jnp.arange(qb.shape[0]) * block

    @jax.checkpoint
    def rows(args):
        qi, lo = args
        sc = num.einsum("bqhd,bkhd->bhqk", qi, k) * d ** -0.5
        keep = (lo + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return num.einsum("bhqk,bkhd->bqhd", p, v)

    out = jnp.moveaxis(jax.lax.map(rows, (qb, first)), 0, 1)
    return out.reshape(b, -1, h * v.shape[-1])[:, :s]


def latent_attention(model, num: Numerics, x, p):
    b, s, _ = x.shape
    h, eps = model["num_attention_heads"], model["rms_norm_eps"]
    nope, rot = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rkv, theta = model["kv_lora_rank"], model["rope_theta"]
    proj = lambda t, name: _held(num, num.einsum(  # noqa: E731
        "bsd,de->bse", t, p[name]["kernel"]))
    c_q = _norm(num, proj(x, "q_a_proj"), p["q_a_norm"]["scale"], eps)
    q = proj(c_q, "q_b_proj").reshape(b, s, h, nope + rot)
    kva = proj(x, "kv_a_proj")
    c_kv = _norm(num, kva[..., :rkv], p["kv_a_norm"]["scale"], eps)
    k_r = _held(num, _turn(kva[..., rkv:].reshape(b, s, 1, rot), theta))
    kv = proj(c_kv, "kv_b_proj").reshape(b, s, h, -1)
    q = jnp.concatenate(
        [q[..., :nope], _held(num, _turn(q[..., nope:], theta))], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, h, rot))], axis=-1)
    a = _held(num, _attention(num, q, k, kv[..., nope:]))
    return _held(num, num.einsum("bse,ed->bsd", a, p["o_proj"]["kernel"]))


def _swiglu(num: Numerics, x, p):
    wg, wu, wd = (p[n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj"))
    h = _held(num, jax.nn.silu(_held(num, num.einsum("td,df->tf", x, wg)))
              * _held(num, num.einsum("td,df->tf", x, wu)))
    return _held(num, num.einsum("tf,fd->td", h, wd))


def route(model, x, p):
    """(chosen (T, k) expert ids, weights (T, k)) in float32 at all passes,
    whatever the numerics: a rounded score moves a token's last expert."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x.astype(jnp.float32), p["router"]["kernel"],
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"],
                              model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * model["routed_scaling_factor"]


def sparse_ffn(model, num: Numerics, x, p, first: int = 0):
    """This chip's share: the held experts are ``first .. first + held`` of
    the router's; ``first`` is 0 in the cell and moves in the test that adds
    the shares up."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, w = route(model, x, p)
    ex = p["experts"]

    @jax.checkpoint
    def one(acc, xs):
        e, kernels = xs
        weight = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(num, x, kernels), None

    held = ex["gate_proj"]["kernel"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(held), ex))
    return _held(num, out + _swiglu(num, x, p["shared_expert"])).reshape(shape)


def dense_ffn(model, num: Numerics, x, p):
    shape = x.shape
    return _swiglu(num, x.reshape(-1, shape[-1]), p).reshape(shape)


def _block(model, num: Numerics, ffn, x, p):
    eps = model["rms_norm_eps"]
    x = _held(num, x + latent_attention(
        model, num, _norm(num, x, p["input_norm"]["scale"], eps), p["mixer"]))
    return _held(num, x + ffn(
        model, num, _norm(num, x, p["post_attn_norm"]["scale"], eps), p["mlp"]))


def hidden(model, params, tokens, num: Numerics):
    """(the trunk's output after the final norm, the prediction block's after
    its own), each (B, S, hidden)."""
    eps = model["rms_norm_eps"]
    embed = lambda t: _held(num, params["embed_tokens"]["embedding"][t])  # noqa: E731
    dense = jax.checkpoint(lambda x, p: _block(model, num, dense_ffn, x, p))
    sparse = jax.checkpoint(lambda x, p: _block(model, num, sparse_ffn, x, p))
    x = embed(tokens)
    for i in range(_depths(model)[0]):
        x = dense(x, params[f"dense_{i}"])
    x, _ = jax.lax.scan(lambda x, p: (sparse(x, p), None), x, params["layers"])
    m = params["mtp"]
    ahead = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    both = jnp.concatenate([_norm(num, x, m["hnorm"]["scale"], eps),
                            _norm(num, embed(ahead), m["enorm"]["scale"], eps)],
                           axis=-1)
    y = sparse(_held(num, num.einsum("bse,ed->bsd", both, m["eh_proj"]["kernel"])),
               m["block"])
    return (_norm(num, x, params["final_norm"]["scale"], eps),
            _norm(num, y, m["final_norm"]["scale"], eps))


def cross_entropy(num: Numerics, h, w, tokens, ahead: int):
    """Mean over the B x (S - ahead) positions that have a target ``ahead``
    places on, over the slice of the vocabulary held here, logits in blocks."""
    h, tgt = h[:, :-ahead], tokens[:, ahead:]
    n = tgt.size
    h, tgt = h.reshape(n, -1), tgt.reshape(n)
    pad = (-n) % LOGIT_BLOCK
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, LOGIT_BLOCK, h.shape[-1])
    tgt = jnp.pad(tgt, (0, pad), constant_values=-1).reshape(-1, LOGIT_BLOCK)

    @jax.checkpoint
    def block(w, hb, tb):
        lp = jax.nn.log_softmax(num.einsum("nd,dv->nv", hb, w))
        picked = jnp.take_along_axis(lp, jnp.maximum(tb, 0)[:, None], 1)[:, 0]
        return -jnp.sum(jnp.where(tb >= 0, picked, 0.0))

    total, _ = jax.lax.scan(lambda acc, xs: (acc + block(w, *xs), None),
                            jnp.zeros((), jnp.float32), (h, tgt))
    return total / n


def loss(model, job, params, batch, num: Numerics = Numerics()):
    tokens = batch["tokens"]
    h, h_mtp = hidden(model, params, tokens, num)
    w = params["lm_head"]["kernel"]
    return (cross_entropy(num, h, w, tokens, 1)
            + model["mtp_lambda"] * cross_entropy(num, h_mtp, w, tokens, 2))
