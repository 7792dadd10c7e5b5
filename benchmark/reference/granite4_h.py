"""Granite-4.0-H-Micro (``model_type`` ``granitemoehybrid``, no routed experts)
as its published configuration describes it, forward and next-token loss in
plain float32: no kernel, no chunked recurrence, nothing of ``tpucfn``.

With ``N`` the RMS norm ``x * rsqrt(mean(x^2) + eps) * w`` (``w`` starts at 1)
and ``r`` the ``residual_multiplier``:

- ``x = embedding_multiplier * Emb(tokens)``; layer ``l`` is ``h = x + r *
  mixer_l(N(x)); y = h + r * mlp(N(h))``; ``mlp(h) = W_down (silu(W_gate h) *
  W_up h)`` with no bias; after the last layer the final norm and ``logits = (h
  Emb^T) / logits_scaling``: the head is the embedding's table.
- ``layer_types[l] == "attention"``: q, k, v, o without bias,
  ``num_attention_heads`` query and ``num_key_value_heads`` key/value heads of
  ``hidden_size / num_attention_heads``, **no positional embedding**
  (``position_embedding_type`` ``nope``), causal softmax of
  ``attention_multiplier * q k^T``.
- ``layer_types[l] == "mamba"`` (Mamba-2): ``[z | xBC | dt] = W_in h`` of widths
  ``heads * head``, ``heads * head + 2 * groups * state`` and ``heads``; ``xBC
  = silu(conv(xBC) + bias)``, a depthwise causal convolution of
  ``mamba_d_conv`` taps; ``xBC`` splits into ``x`` (``mamba_n_heads`` heads of
  ``mamba_d_head``), ``B`` and ``C`` (``mamba_n_groups`` groups of
  ``mamba_d_state``, each shared by its heads); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; then, **position by position**, per head
  ``H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T`` from ``H_0 = 0`` and ``y_t =
  H_t C_t + D x_t``; ``y = N(y * silu(z))`` over all the heads' channels at
  once, the gate before the norm; ``out = W_out y``.

Departures from the published code, all listed in the configuration's file:
``W_gate`` and ``W_up`` are leaves of their own (the published
``shared_mlp.input_linear`` holds them side by side), so that each gradient is
compared as a leaf; ``A_log`` and ``dt_bias`` are drawn normal around the
published initialiser's ranges, ``D`` is 1.

The control (``Numerics("fp8")``) rounds the operands of every product and, via
``_held``, every tensor the configuration's compute dtype holds between them
(the embedding's rows, a norm's result, a product's result, the convolution's,
the gated output, the residual sums), as ``benchmark/reference/qwen3_next.py``
does; the step ``dt``, the decays and the state are float32 in the program and
stay so here.  In float32 ``_held`` changes nothing.

Layers are stacked as the program's runs are: a period of ``layer_types`` (its
shortest repeating prefix) is laid out as runs of one kind, ``periods/run<i>_
<kind>`` holding ``(periods, run length, ...)``, or ``(periods, ...)`` for a run
of one.  To fit one chip in float32 a layer's activations are recomputed in the
backward pass, the recurrence is a scan of checkpointed blocks of positions,
scores are made a block of queries at a time and the head's logits in blocks;
none of it changes a value.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

from benchmark.reference.joyai_llm_flash import cross_entropy
from benchmark.reference.numerics import Numerics

QUERY_BLOCK = 256
SCAN_BLOCK = 128     # positions of the recurrence between checkpoints


def layout(model) -> tuple[int, list[tuple[str, int]]]:
    """(periods, the runs of one period as (kind, length))."""
    types = tuple(model["layer_types"])
    n = len(types)
    if n != model["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and types[:p] * (n // p) == types)
    return n // p, [(kind, len(list(group)))
                    for kind, group in itertools.groupby(types[:p])]


def _mamba_sizes(model):
    heads, head = model["mamba_n_heads"], model["mamba_d_head"]
    if heads * head != model["mamba_expand"] * model["hidden_size"]:
        raise ValueError("mamba_n_heads * mamba_d_head is not the expanded width")
    return heads, head, model["mamba_d_state"], model["mamba_n_groups"]


def _layer_spec(model, kind, lead):
    d, std, f = (model["hidden_size"], model["initializer_range"],
                 model["shared_intermediate_size"])
    common = {
        "input_norm/scale": (lead + (d,), 1.0, 0.0),
        "post_attn_norm/scale": (lead + (d,), 1.0, 0.0),
        "mlp/gate_proj/kernel": (lead + (d, f), 0.0, std),
        "mlp/up_proj/kernel": (lead + (d, f), 0.0, std),
        "mlp/down_proj/kernel": (lead + (f, d), 0.0, std),
    }
    if kind == "attention":
        nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
        hd = d // nq
        return {**common,
                "mixer/q_proj/kernel": (lead + (d, nq * hd), 0.0, std),
                "mixer/k_proj/kernel": (lead + (d, nkv * hd), 0.0, std),
                "mixer/v_proj/kernel": (lead + (d, nkv * hd), 0.0, std),
                "mixer/o_proj/kernel": (lead + (nq * hd, d), 0.0, std)}
    heads, head, state, groups = _mamba_sizes(model)
    inner, width = heads * head, model["mamba_d_conv"]
    conv = inner + 2 * groups * state
    return {**common,
            "mixer/in_proj/kernel": (lead + (d, inner + conv + heads), 0.0, std),
            # the published layer draws a depthwise filter of fan-in `width`
            "mixer/conv/kernel": (lead + (width, conv), 0.0, width ** -0.5),
            "mixer/conv_bias": (lead + (conv,), 0.0, model["conv_bias_std"]),
            "mixer/A_log": (lead + (heads,), model["a_log_mean"],
                            model["a_log_std"]),
            "mixer/dt_bias": (lead + (heads,), model["dt_bias_mean"],
                              model["dt_bias_std"]),
            "mixer/D": (lead + (heads,), 1.0, 0.0),
            "mixer/norm/scale": (lead + (inner,), 1.0, 0.0),
            "mixer/out_proj/kernel": (lead + (inner, d), 0.0, std)}


def param_spec(model) -> dict:
    if model["num_local_experts"] or not model["tie_word_embeddings"]:
        raise ValueError("no routed experts and a tied head")
    d, std = model["hidden_size"], model["initializer_range"]
    periods, runs = layout(model)
    spec = {"embed_tokens/embedding": ((model["vocab_size"], d), 0.0, std)}
    for i, (kind, length) in enumerate(runs):
        lead = (periods,) if length == 1 else (periods, length)
        spec.update({f"periods/run{i}_{kind}/{k}": s
                     for k, s in _layer_spec(model, kind, lead).items()})
    spec["final_norm/scale"] = ((d,), 1.0, 0.0)
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


def _proj(num: Numerics, x, p, name):
    return _held(num, num.einsum("bsd,de->bse", x, p[name]["kernel"]))


def attention(model, num: Numerics, x, p):
    """Causal softmax attention with the model's own scale and no positional
    embedding, a block of queries at a time."""
    b, s, d = x.shape
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, scale = d // nq, model["attention_multiplier"]
    q = _proj(num, x, p, "q_proj").reshape(b, s, nq, hd)
    k, v = (jnp.repeat(_proj(num, x, p, name).reshape(b, s, nkv, hd),
                       nq // nkv, axis=2) for name in ("k_proj", "v_proj"))
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(b, -1, block, nq, hd), 1, 0)
    first = jnp.arange(qb.shape[0]) * block

    @jax.checkpoint
    def rows(args):
        qi, lo = args
        sc = num.einsum("bqhd,bkhd->bhqk", qi, k) * scale
        keep = (lo + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return num.einsum("bhqk,bkhd->bqhd", pr, v)

    out = jnp.moveaxis(jax.lax.map(rows, (qb, first)), 0, 1)
    out = _held(num, out.reshape(b, -1, nq * hd)[:, :s])
    return _held(num, num.einsum("bse,ed->bsd", out, p["o_proj"]["kernel"]))


def recurrence(num: Numerics, x, dt, a, b, c, d):
    """The state-space recurrence position by position.  x: (B,S,G,R,P), heads
    as (group, head in group); dt: (B,S,G,R); a, d: (G,R); b, c: (B,S,G,N).
    Returns y (B,S,G,R,P)."""
    bsz, s, g, r, p = x.shape
    n = b.shape[-1]

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * a)[..., None, None] + num.einsum(
            "bgrp,bgn->bgrpn", _held(num, dt_t[..., None] * x_t), b_t)
        return state, num.einsum("bgrpn,bgn->bgrp", state, c_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    blocks = -(-s // SCAN_BLOCK)
    pad = blocks * SCAN_BLOCK - s     # padded positions: dt 0, x 0: no change

    def cut(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((blocks, SCAN_BLOCK) + t.shape[1:])

    _, y = jax.lax.scan(block, jnp.zeros((bsz, g, r, p, n), jnp.float32),
                        tuple(cut(t) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y.reshape((blocks * SCAN_BLOCK,) + y.shape[2:])[:s], 0, 1)
    return y + d[..., None] * x


def gated_norm(num: Numerics, y, z, w, eps):
    """The gate before the norm, one norm over all the heads' channels."""
    return _norm(num, y * jax.nn.silu(z), w, eps)


def mamba(model, num: Numerics, x, p):
    bsz, s, _ = x.shape
    heads, head, state, g = _mamba_sizes(model)
    inner = heads * head
    conv = inner + 2 * g * state
    z, xbc, dt = jnp.split(_proj(num, x, p, "in_proj"), [inner, inner + conv],
                           axis=-1)
    w = p["conv"]["kernel"]                                # (width, channels)
    width = w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = _held(num, jax.nn.silu(
        sum(padded[:, j:j + s] * w[j] for j in range(width)) + p["conv_bias"]))
    xs, b, c = jnp.split(xbc, [inner, inner + g * state], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    per_group = lambda t: t.reshape(g, heads // g)  # noqa: E731
    y = recurrence(
        num, xs.reshape(bsz, s, g, heads // g, head),
        dt.reshape(bsz, s, g, heads // g), per_group(-jnp.exp(p["A_log"])),
        b.reshape(bsz, s, g, state), c.reshape(bsz, s, g, state),
        per_group(p["D"]))
    y = gated_norm(num, _held(num, y).reshape(bsz, s, inner), z,
                   p["norm"]["scale"], model["rms_norm_eps"])
    return _held(num, num.einsum("bse,ed->bsd", y, p["out_proj"]["kernel"]))


def mlp(num: Numerics, x, p):
    gate, up = _proj(num, x, p, "gate_proj"), _proj(num, x, p, "up_proj")
    return _held(num, num.einsum("bsf,fd->bsd", _held(num, jax.nn.silu(gate) * up),
                                 p["down_proj"]["kernel"]))


MIXERS = {"attention": attention, "mamba": mamba}


def layer(model, num: Numerics, kind: str, x, p):
    eps, r = model["rms_norm_eps"], model["residual_multiplier"]
    x = _held(num, x + r * MIXERS[kind](
        model, num, _norm(num, x, p["input_norm"]["scale"], eps), p["mixer"]))
    return _held(num, x + r * mlp(
        num, _norm(num, x, p["post_attn_norm"]["scale"], eps), p["mlp"]))


def hidden(model, params, tokens, num: Numerics):
    """The last layer's output after the final norm, (B, S, hidden)."""
    _, runs = layout(model)
    one = {kind: jax.checkpoint(lambda x, p, kind=kind: layer(
        model, num, kind, x, p)) for kind in MIXERS}

    def period(x, p):
        for i, (kind, length) in enumerate(runs):
            run = p[f"run{i}_{kind}"]
            if length == 1:
                x = one[kind](x, run)
            else:
                x, _ = jax.lax.scan(
                    lambda x, q, kind=kind: (one[kind](x, q), None), x, run)
        return x, None

    x = _held(num, model["embedding_multiplier"]
              * _held(num, params["embed_tokens"]["embedding"][tokens]))
    x, _ = jax.lax.scan(period, x, params["periods"])
    return _norm(num, x, params["final_norm"]["scale"], model["rms_norm_eps"])


def loss(model, job, params, batch, num: Numerics = Numerics()):
    tokens = batch["tokens"]
    h = hidden(model, params, tokens, num)
    return cross_entropy(num, _held(num, h / model["logits_scaling"]),
                         params["embed_tokens"]["embedding"].T, tokens, 1)
