"""Olmo-Hybrid-7B (``model_type`` ``olmo_hybrid``) as its published
configuration describes it, forward and next-token loss in plain float32: no
kernel, no chunked recurrence, nothing of ``tpucfn``.

With ``N`` the RMS norm ``x * rsqrt(mean(x^2) + eps) * w`` (``w`` starts at 1,
not zero-centred), every layer is Olmo's reordered block, ``h = x +
N(mixer(x)); y = h + N(mlp(h))``: the norms sit on what a branch returns, not
on what it takes.  ``mlp(h) = W_down (silu(W_gate h) * W_up h)``, no bias
anywhere.  After the last layer a final ``N`` and an untied head.

- ``layer_types[l] == "linear_attention"`` (Gated DeltaNet): a depthwise
  causal convolution of ``linear_conv_kernel_dim`` taps without bias and SiLU
  over ``[q | k | v]``; q and k of ``linear_num_key_heads`` heads of
  ``linear_key_head_dim``, v of ``linear_num_value_heads`` heads of
  ``linear_value_head_dim``, each key head serving ``Hv / Hk`` value heads; q
  and k scaled to unit length per head, q times ``Dk^-1/2``; ``beta = 2 *
  sigmoid(b)`` under ``linear_allow_neg_eigval`` (``sigmoid(b)`` without), ``g
  = -exp(A_log) * softplus(a + dt_bias)``; the delta rule **position by
  position** (``qwen3_next.delta_rule``, which takes ``beta`` as given); ``o``
  RMS-normed per value head, times ``SiLU(z)``; ``out_proj``.
- ``layer_types[l] == "full_attention"``: multi-head attention of
  ``num_attention_heads`` query and ``num_key_value_heads`` key/value heads of
  ``hidden_size / num_attention_heads``; Olmo's QK-norm, ``N`` over the whole
  query projection and another over the whole key projection before the heads
  are split; **no positional embedding** (``rope_parameters.rope_theta`` is
  null); causal softmax at ``head_dim^-1/2``.

Departures from the published code, all listed in the configuration's file:
separate q, k and v projections and their taps, for the published layer's one
convolution over ``[q | k | v]`` (a depthwise convolution is the same over
each channel); ``A_log`` and ``dt_bias`` drawn normal around the published
initialiser's ranges.

The control (``Numerics("fp8")``) rounds the operands of every product and,
via ``_held``, every tensor the configuration's compute dtype holds between
them, as ``benchmark/reference/qwen3_next.py`` does; ``beta``, the decays and
the state are float32 in the program and stay so here.

Layers are stacked as the program's scanned periods are: ``periods/linear``
holds ``(periods, interval - 1, ...)`` and ``periods/full`` ``(periods,
...)``.  To fit one chip in float32 a layer's activations are recomputed in
the backward pass, the recurrence is checkpointed by blocks of positions,
each part of a DeltaNet layer's ``[q | k | v]`` and its gated output are
recomputed on their own, scores are made a block of queries at a time, the
feed-forward and the head's logits a block of positions at a time; none of it changes a value in float32
(the control scales its fp8 operands a block at a time there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.granite4_h import mlp
from benchmark.reference.joyai_llm_flash import cross_entropy
from benchmark.reference.numerics import Numerics
from benchmark.reference.qwen3_next import delta_rule

QUERY_BLOCK = 256
MLP_BLOCK = 2048     # positions of the feed-forward a step of its map


def layout(model) -> tuple[int, int]:
    """(periods, DeltaNet layers a period): ``layer_types`` is a whole number
    of periods of linear layers ended by one full-attention layer."""
    types = list(model["layer_types"])
    if len(types) != model["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    interval = types.index("full_attention") + 1
    period = ["linear_attention"] * (interval - 1) + ["full_attention"]
    if types != period * (len(types) // interval):
        raise ValueError("layer_types is no repetition of linear layers "
                         "ended by one full-attention layer")
    return len(types) // interval, interval - 1


def _block_spec(model, lead, d, std):
    f = model["intermediate_size"]
    return {
        "post_mixer_norm/scale": (lead + (d,), 1.0, 0.0),
        "post_mlp_norm/scale": (lead + (d,), 1.0, 0.0),
        "mlp/gate_proj/kernel": (lead + (d, f), 0.0, std),
        "mlp/up_proj/kernel": (lead + (d, f), 0.0, std),
        "mlp/down_proj/kernel": (lead + (f, d), 0.0, std),
    }


def param_spec(model) -> dict:
    if model["tie_word_embeddings"] or model["attention_bias"]:
        raise ValueError("an untied head and no bias")
    d, std = model["hidden_size"], model["initializer_range"]
    periods, n_lin = layout(model)
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, width = d // nq, model["linear_conv_kernel_dim"]
    lin, full = (periods, n_lin), (periods,)
    linear = {
        "mixer/q_proj/kernel": (lin + (d, hk * dk), 0.0, std),
        "mixer/k_proj/kernel": (lin + (d, hk * dk), 0.0, std),
        "mixer/v_proj/kernel": (lin + (d, hv * dv), 0.0, std),
        "mixer/z_proj/kernel": (lin + (d, hv * dv), 0.0, std),
        "mixer/b_proj/kernel": (lin + (d, hv), 0.0, std),
        "mixer/a_proj/kernel": (lin + (d, hv), 0.0, std),
        # the published layer draws a depthwise filter of fan-in `width`
        "mixer/conv/kernel": (lin + (width, 2 * hk * dk + hv * dv), 0.0,
                              width ** -0.5),
        "mixer/A_log": (lin + (hv,), model["a_log_mean"], model["a_log_std"]),
        "mixer/dt_bias": (lin + (hv,), model["dt_bias_mean"],
                          model["dt_bias_std"]),
        "mixer/norm/scale": (lin + (dv,), 1.0, 0.0),
        "mixer/out_proj/kernel": (lin + (hv * dv, d), 0.0, std),
        **_block_spec(model, lin, d, std),
    }
    attn = {
        "mixer/q_proj/kernel": (full + (d, nq * hd), 0.0, std),
        "mixer/k_proj/kernel": (full + (d, nkv * hd), 0.0, std),
        "mixer/v_proj/kernel": (full + (d, nkv * hd), 0.0, std),
        "mixer/q_norm/scale": (full + (nq * hd,), 1.0, 0.0),
        "mixer/k_norm/scale": (full + (nkv * hd,), 1.0, 0.0),
        "mixer/o_proj/kernel": (full + (nq * hd, d), 0.0, std),
        **_block_spec(model, full, d, std),
    }
    return {
        "embed_tokens/embedding": ((model["vocab_size"], d), 0.0, std),
        **{f"periods/linear/{k}": s for k, s in linear.items()},
        **{f"periods/full/{k}": s for k, s in attn.items()},
        "final_norm/scale": ((d,), 1.0, 0.0),
        "lm_head/kernel": ((d, model["vocab_size"]), 0.0, std),
    }


def state_spec(model) -> dict:
    return {}


def _held(num: Numerics, x):
    """A tensor held in the configuration's compute dtype: float32 here, and
    in the control rounded as a product's operand is."""
    return num.operand(x)


def _norm(num: Numerics, x, w, eps):
    return _held(num, x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w)


def _proj(num: Numerics, x, p, name):
    return _held(num, num.einsum("bsd,de->bse", x, p[name]["kernel"]))


def attention(model, num: Numerics, x, p):
    """QK-norm over the whole projections, no positional embedding, causal
    softmax at the head size's root, a block of queries at a time."""
    b, s, d = x.shape
    nq, nkv, eps = (model["num_attention_heads"], model["num_key_value_heads"],
                    model["rms_norm_eps"])
    hd = d // nq
    q = _norm(num, _proj(num, x, p, "q_proj"), p["q_norm"]["scale"], eps)
    k = _norm(num, _proj(num, x, p, "k_proj"), p["k_norm"]["scale"], eps)
    q = q.reshape(b, s, nq, hd)
    k, v = (jnp.repeat(t.reshape(b, s, nkv, hd), nq // nkv, axis=2)
            for t in (k, _proj(num, x, p, "v_proj")))
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(b, -1, block, nq, hd), 1, 0)
    first = jnp.arange(qb.shape[0]) * block

    @jax.checkpoint
    def rows(args):
        qi, lo = args
        sc = num.einsum("bqhd,bkhd->bhqk", qi, k) * hd ** -0.5
        keep = (lo + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return num.einsum("bhqk,bkhd->bqhd", pr, v)

    out = jnp.moveaxis(jax.lax.map(rows, (qb, first)), 0, 1)
    out = _held(num, out.reshape(b, -1, nq * hd)[:, :s])
    return _held(num, num.einsum("bse,ed->bsd", out, p["o_proj"]["kernel"]))


def _conv_silu(num: Numerics, x, kernel, taps):
    """One part of ``[q | k | v]``: its projection, then its channels of the
    depthwise causal convolution and SiLU."""
    s, width = x.shape[1], taps.shape[0]
    t = _held(num, num.einsum("bsd,de->bse", x, kernel))
    padded = jnp.pad(t, ((0, 0), (width - 1, 0), (0, 0)))
    return _held(num, jax.nn.silu(
        sum(padded[:, j:j + s] * taps[j] for j in range(width))))


def _gated_output(model, num: Numerics, o, x, norm, z_kernel, out_kernel):
    b, s, hv, dv = o.shape
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + model["rms_norm_eps"]) * norm
    z = _held(num, num.einsum("bsd,de->bse", x, z_kernel)).reshape(b, s, hv, dv)
    o = _held(num, o * jax.nn.silu(z))
    return _held(num, num.einsum("bse,ed->bsd", o.reshape(b, s, hv * dv),
                                 out_kernel))


def gated_delta_net(model, num: Numerics, x, p):
    """Each part of ``[q | k | v]`` and the gated output are recomputed on
    their own in the backward pass (float32 at 16,384 positions)."""
    b, s, _ = x.shape
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    taps = p["conv"]["kernel"]                             # (width, channels)
    part = jax.checkpoint(functools.partial(_conv_silu, num))
    q = part(x, p["q_proj"]["kernel"], taps[:, :hk * dk])
    k = part(x, p["k_proj"]["kernel"], taps[:, hk * dk:2 * hk * dk])
    v = part(x, p["v_proj"]["kernel"], taps[:, 2 * hk * dk:])
    unit = lambda t: _held(num, t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6))
    rep = hv // hk
    q = jnp.repeat(unit(q.reshape(b, s, hk, dk)) * dk ** -0.5, rep, axis=2)
    k = jnp.repeat(unit(k.reshape(b, s, hk, dk)), rep, axis=2)
    scale = 2.0 if model["linear_allow_neg_eigval"] else 1.0
    beta = scale * jax.nn.sigmoid(_proj(num, x, p, "b_proj"))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(_proj(num, x, p, "a_proj")
                                               + p["dt_bias"])
    o = _held(num, delta_rule(num, q, k, v.reshape(b, s, hv, dv), g, beta))
    return jax.checkpoint(functools.partial(_gated_output, model, num))(
        o, x, p["norm"]["scale"], p["z_proj"]["kernel"], p["out_proj"]["kernel"])


def mlp_in_blocks(num: Numerics, x, p):
    """``granite4_h.mlp`` a block of positions at a time."""
    b, s, d = x.shape
    block = min(MLP_BLOCK, s)
    pad = (-s) % block
    xb = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, block, d)
    out = jax.lax.map(jax.checkpoint(lambda t: mlp(num, t, p)),
                      jnp.moveaxis(xb, 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, d)[:, :s]


def layer(model, num: Numerics, mixer, x, p):
    eps = model["rms_norm_eps"]
    x = _held(num, x + _norm(num, mixer(model, num, x, p["mixer"]),
                             p["post_mixer_norm"]["scale"], eps))
    return _held(num, x + _norm(num, mlp_in_blocks(num, x, p["mlp"]),
                                p["post_mlp_norm"]["scale"], eps))


def hidden(model, params, tokens, num: Numerics):
    """The last layer's output after the final norm, (B, S, hidden)."""
    linear = jax.checkpoint(
        lambda x, p: (layer(model, num, gated_delta_net, x, p), None))
    full = jax.checkpoint(lambda x, p: layer(model, num, attention, x, p))

    def period(x, p):
        x, _ = jax.lax.scan(linear, x, p["linear"])
        return full(x, p["full"]), None

    x = _held(num, params["embed_tokens"]["embedding"][tokens])
    x, _ = jax.lax.scan(period, x, params["periods"])
    return _norm(num, x, params["final_norm"]["scale"], model["rms_norm_eps"])


def loss(model, job, params, batch, num: Numerics = Numerics()):
    """Mean next-token cross-entropy over the B x (S - 1) predicted positions
    and the slice of the vocabulary held here, the logits in blocks."""
    tokens = batch["tokens"]
    return cross_entropy(num, hidden(model, params, tokens, num),
                         params["lm_head"]["kernel"], tokens, 1)
