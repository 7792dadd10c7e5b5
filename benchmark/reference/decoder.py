"""A pre-norm decoder as Mistral-7B-v0.3 publishes it (RMSNorm, grouped-query
attention with rotary embeddings in the half-split layout, SwiGLU, untied
head, no sliding window), forward and next-token loss in plain float32.

Layers are stacked on a leading axis, as the program's scanned layers are.
To fit one chip in float32 the scores are made one group of query heads at a
time, a layer's activations are recomputed in the backward pass, and the
head's logits are made in blocks of positions; none of it changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.numerics import Numerics

LOGIT_BLOCK = 1024


def param_spec(model) -> dict:
    d, L = model["hidden_size"], model["num_hidden_layers"]
    hd = model["head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    f, v = model["intermediate_size"], model["vocab_size"]
    std = model["initializer_range"]
    return {
        "embed_tokens/embedding": ((v, d), 0.0, std),
        "layers/input_norm/scale": ((L, d), 1.0, 0.0),
        "layers/attn/q_proj/kernel": ((L, d, nq * hd), 0.0, std),
        "layers/attn/k_proj/kernel": ((L, d, nkv * hd), 0.0, std),
        "layers/attn/v_proj/kernel": ((L, d, nkv * hd), 0.0, std),
        "layers/attn/o_proj/kernel": ((L, nq * hd, d), 0.0, std),
        "layers/post_attn_norm/scale": ((L, d), 1.0, 0.0),
        "layers/mlp/gate_proj/kernel": ((L, d, f), 0.0, std),
        "layers/mlp/up_proj/kernel": ((L, d, f), 0.0, std),
        "layers/mlp/down_proj/kernel": ((L, f, d), 0.0, std),
        "final_norm/scale": ((d,), 1.0, 0.0),
        "lm_head/kernel": ((d, v), 0.0, std),
    }


def state_spec(model) -> dict:
    return {}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    # x: (B, S, H, D); pairs are (i, i + D/2), as in the published modelling code
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _attention(num: Numerics, q, k, v):
    """Causal softmax attention; q (B,S,Hq,D), k and v (B,S,Hkv,D).  One
    group of query heads (those sharing a key head) at a time."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, s, hkv, hq // hkv, d)
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args  # (B,S,G,D), (B,S,D), (B,S,D)
        sc = num.einsum("bqgd,bkd->bgqk", qg, kg) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return num.einsum("bgqk,bkd->bqgd", p, vg)

    out = jax.lax.map(group, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                              jnp.moveaxis(v, 2, 0)))      # (Hkv,B,S,G,D)
    return jnp.moveaxis(out, 0, 2).reshape(b, s, hq * d)


def _layer(model, num: Numerics, x, p):
    b, s, _ = x.shape
    hd = model["head_dim"]
    h = _rms(x, p["input_norm"]["scale"], model["rms_norm_eps"])
    proj = lambda name: num.einsum(  # noqa: E731
        "bsd,de->bse", h, p["attn"][name]["kernel"]).reshape(b, s, -1, hd)
    q = _rope(proj("q_proj"), model["rope_theta"])
    k = _rope(proj("k_proj"), model["rope_theta"])
    a = _attention(num, q, k, proj("v_proj"))
    x = x + num.einsum("bse,ed->bsd", a, p["attn"]["o_proj"]["kernel"])
    h = _rms(x, p["post_attn_norm"]["scale"], model["rms_norm_eps"])
    gate = jax.nn.silu(num.einsum("bsd,df->bsf", h, p["mlp"]["gate_proj"]["kernel"]))
    up = num.einsum("bsd,df->bsf", h, p["mlp"]["up_proj"]["kernel"])
    return x + num.einsum("bsf,fd->bsd", gate * up, p["mlp"]["down_proj"]["kernel"])


def hidden(model, params, tokens, num: Numerics):
    x = params["embed_tokens"]["embedding"][tokens]
    body = jax.checkpoint(lambda x, p: (_layer(model, num, x, p), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rms(x, params["final_norm"]["scale"], model["rms_norm_eps"])


def loss(model, job, params, batch, num: Numerics = Numerics()):
    """Mean next-token cross-entropy over the B x (S-1) predicted positions."""
    tokens = batch["tokens"]
    h = hidden(model, params, tokens, num)[:, :-1]
    tgt = tokens[:, 1:]
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

    def body(acc, xs):
        return acc + block(params["lm_head"]["kernel"], *xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, tgt))
    return total / n
