"""One chip's share of Qwen3-Next-80B-A3B as its published modelling code
computes it, forward and next-token loss in plain float32: no kernel, no
chunked recurrence, nothing of ``tpucfn``.

Layer ``i`` is ``h = x + mixer_i(N(x)); y = h + ffn(N(h))`` with ``N`` the
zero-centred RMS norm ``x * rsqrt(mean(x^2) + eps) * (1 + w)``; the mixer is
gated softmax attention when ``(i + 1) % full_attention_interval == 0`` and
Gated DeltaNet otherwise; every feed-forward is sparse.

- Gated attention: ``[q | gate]`` a head from one projection, q and k
  normalised per head, rotary embedding on the first ``partial_rotary_factor``
  of the head's dims (half-split pairs), causal softmax, the output times
  ``sigmoid(gate)``.
- Gated DeltaNet: depthwise causal convolution (no bias) and SiLU over
  ``[q | k | v]``; unit q (times ``dk^-1/2``) and k; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) * softplus(a + dt_bias)``; the delta rule **position by
  position**, ``S <- exp(g) S; u = beta (v - S^T k); S <- S + k u^T; o = S^T
  q``; ``o`` RMS-normed per head, times ``SiLU(z)``.
- Sparse feed-forward: softmax over all ``router_experts``, the
  ``num_experts_per_tok`` largest renormalised to sum 1; this chip's share is
  the sum over the chosen experts among the ``num_experts`` held (the first
  ones), a loop over them with the weights as masks; the shared expert, times
  ``sigmoid(x w_s)``, whole.

Departures from the published code, all listed in the configuration's file:
separate q, k, v, z (and b, a) projections for the interleaved ``in_proj_qkvz``
(``in_proj_ba``) layout, a permutation of columns; no multi-token-prediction
module; no auxiliary router loss.

The control (``Numerics("fp8")``) is this reference in the nearest precision
below the configuration's bfloat16, in every place where that dtype is: the
operands of every product (``num.einsum``), and every tensor the configuration's
compute dtype holds between them (``_held``: the embedding's rows, a norm's
result, a product's result, the convolution's, an elementwise gate's, the
residual sums).  In float32 ``_held`` changes nothing.  With the products'
operands alone the control read as close to float32 as the bfloat16 program
does at the cell's size (PERF.md, Findings, PR 27).

Layers are stacked as the program's scanned periods are: ``periods/linear``
holds ``(periods, interval - 1, ...)`` and ``periods/full`` ``(periods,
...)``.  To fit one chip in float32 a layer's activations are recomputed in
the backward pass, the recurrence is checkpointed by blocks of positions,
scores are made one group of query heads at a time, experts one at a time and
the head's logits in blocks; none of it changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import LOGIT_BLOCK, _attention, _rope
from benchmark.reference.numerics import Numerics

SCAN_BLOCK = 128     # positions of the recurrence between checkpoints


def _sizes(model):
    d = model["hidden_size"]
    interval = model["full_attention_interval"]
    periods, rest = divmod(model["num_hidden_layers"], interval)
    if rest:
        raise ValueError("the depth is no whole number of periods")
    return d, periods, interval - 1


def param_spec(model) -> dict:
    d, p, n_lin = _sizes(model)
    std = model["initializer_range"]
    hd, nq, nkv = (model["head_dim"], model["num_attention_heads"],
                   model["num_key_value_heads"])
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    width = model["linear_conv_kernel_dim"]
    e_all, e_held = model["router_experts"], model["num_experts"]
    f, fs = model["moe_intermediate_size"], model["shared_expert_intermediate_size"]
    v = model["vocab_size"]

    def ffn(lead):
        return {
            "post_attn_norm/scale": (lead + (d,), 0.0, 0.0),
            "mlp/router/kernel": (lead + (d, e_all), 0.0, std),
            "mlp/experts/gate_proj/kernel": (lead + (e_held, d, f), 0.0, std),
            "mlp/experts/up_proj/kernel": (lead + (e_held, d, f), 0.0, std),
            "mlp/experts/down_proj/kernel": (lead + (e_held, f, d), 0.0, std),
            "mlp/shared_expert/gate_proj/kernel": (lead + (d, fs), 0.0, std),
            "mlp/shared_expert/up_proj/kernel": (lead + (d, fs), 0.0, std),
            "mlp/shared_expert/down_proj/kernel": (lead + (fs, d), 0.0, std),
            "mlp/shared_expert_gate/kernel": (lead + (d, 1), 0.0, std),
        }

    lin, full = (p, n_lin), (p,)
    linear = {
        "input_norm/scale": (lin + (d,), 0.0, 0.0),
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
        "mixer/dt_bias": (lin + (hv,), 1.0, 0.0),
        "mixer/norm/scale": (lin + (dv,), 1.0, 0.0),
        "mixer/out_proj/kernel": (lin + (hv * dv, d), 0.0, std),
        **ffn(lin),
    }
    attn = {
        "input_norm/scale": (full + (d,), 0.0, 0.0),
        "mixer/q_proj/kernel": (full + (d, nq * 2 * hd), 0.0, std),
        "mixer/k_proj/kernel": (full + (d, nkv * hd), 0.0, std),
        "mixer/v_proj/kernel": (full + (d, nkv * hd), 0.0, std),
        "mixer/q_norm/scale": (full + (hd,), 0.0, 0.0),
        "mixer/k_norm/scale": (full + (hd,), 0.0, 0.0),
        "mixer/o_proj/kernel": (full + (nq * hd, d), 0.0, std),
        **ffn(full),
    }
    return {
        "embed_tokens/embedding": ((v, d), 0.0, std),
        **{f"periods/linear/{k}": s for k, s in linear.items()},
        **{f"periods/full/{k}": s for k, s in attn.items()},
        "final_norm/scale": ((d,), 0.0, 0.0),
        "lm_head/kernel": ((d, v), 0.0, std),
    }


def state_spec(model) -> dict:
    return {}


def _held(num: Numerics, x):
    """A tensor held in the configuration's compute dtype: float32 here, and
    in the control rounded as a product's operand is."""
    return num.operand(x)


def _norm(num: Numerics, x, w, eps):
    return _held(num, x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w))


def gated_attention(model, num: Numerics, x, p):
    b, s, _ = x.shape
    hd, eps = model["head_dim"], model["rms_norm_eps"]
    proj = lambda name: _held(num, num.einsum(  # noqa: E731
        "bsd,de->bse", x, p[name]["kernel"]))
    qg = proj("q_proj").reshape(b, s, -1, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = proj("k_proj").reshape(b, s, -1, hd)
    v = proj("v_proj").reshape(b, s, -1, hd)
    q = _norm(num, q, p["q_norm"]["scale"], eps)
    k = _norm(num, k, p["k_norm"]["scale"], eps)
    rot = int(hd * model["partial_rotary_factor"])
    turn = lambda t: _held(num, jnp.concatenate(  # noqa: E731
        [_rope(t[..., :rot], model["rope_theta"]), t[..., rot:]], axis=-1))
    a = _held(num, _attention(num, turn(q), turn(k), v))  # (B,S,Hq*hd)
    a = _held(num, a * jax.nn.sigmoid(gate.reshape(b, s, -1)))
    return _held(num, num.einsum("bse,ed->bsd", a, p["o_proj"]["kernel"]))


def delta_rule(num: Numerics, q, k, v, g, beta):
    """The recurrence position by position.  q, k: (B,S,H,Dk) (a key head
    repeated for the value heads it serves); v: (B,S,H,Dv); g, beta: (B,S,H)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[..., None, None]
        u = _held(num, bt[..., None] * (vt - num.einsum("bhkv,bhk->bhv", state, kt)))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, num.einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    n = -(-s // SCAN_BLOCK)
    pad = n * SCAN_BLOCK - s        # padded positions: beta 0, g 0: no change

    def blocks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((n, SCAN_BLOCK) + x.shape[1:])

    _, out = jax.lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32),
                          tuple(blocks(x) for x in (q, k, v, g, beta)))
    out = out.reshape((n * SCAN_BLOCK,) + out.shape[2:])[:s]
    return jnp.moveaxis(out, 0, 1)


def gated_delta_net(model, num: Numerics, x, p):
    b, s, _ = x.shape
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    proj = lambda name: _held(num, num.einsum(  # noqa: E731
        "bsd,de->bse", x, p[name]["kernel"]))
    qkv = jnp.concatenate([proj("q_proj"), proj("k_proj"), proj("v_proj")], -1)
    w = p["conv"]["kernel"]                                # (width, channels)
    width = w.shape[0]
    padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    qkv = _held(num, jax.nn.silu(
        sum(padded[:, j:j + s] * w[j] for j in range(width))))
    q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
    unit = lambda t: _held(num, t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6))
    rep = hv // hk
    q = jnp.repeat(unit(q.reshape(b, s, hk, dk)) * dk ** -0.5, rep, axis=2)
    k = jnp.repeat(unit(k.reshape(b, s, hk, dk)), rep, axis=2)
    beta = jax.nn.sigmoid(proj("b_proj"))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(proj("a_proj") + p["dt_bias"])
    o = _held(num, delta_rule(num, q, k, v.reshape(b, s, hv, dv), g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + model["rms_norm_eps"]) * p["norm"]["scale"]
    o = _held(num, o * jax.nn.silu(proj("z_proj").reshape(b, s, hv, dv)))
    return _held(num, num.einsum("bse,ed->bsd", o.reshape(b, s, hv * dv),
                                 p["out_proj"]["kernel"]))


def _swiglu(num: Numerics, x, wg, wu, wd):
    h = _held(num, jax.nn.silu(_held(num, num.einsum("td,df->tf", x, wg)))
              * _held(num, num.einsum("td,df->tf", x, wu)))
    return _held(num, num.einsum("tf,fd->td", h, wd))


def sparse_ffn(model, num: Numerics, x, p, first: int = 0):
    """This chip's share: the held experts are ``first .. first + held`` of
    the router's; ``first`` is 0 in the cell and moves in the test that adds
    the shares up."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    k = model["num_experts_per_tok"]
    probs = jax.nn.softmax(num.einsum("td,de->te", x, p["router"]["kernel"]), -1)
    top, chosen = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    ex = p["experts"]

    @jax.checkpoint
    def one(acc, xs):
        e, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(chosen == first + e, top, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(num, x, wg, wu, wd), None

    held = ex["gate_proj"]["kernel"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(held), ex["gate_proj"]["kernel"], ex["up_proj"]["kernel"],
        ex["down_proj"]["kernel"]))
    se = p["shared_expert"]
    shared = _swiglu(num, x, se["gate_proj"]["kernel"], se["up_proj"]["kernel"],
                     se["down_proj"]["kernel"])
    gate = jax.nn.sigmoid(_held(num, num.einsum(
        "td,do->to", x, p["shared_expert_gate"]["kernel"])))
    return _held(num, out + _held(num, gate * shared)).reshape(shape)


def _layer(model, num: Numerics, mixer, x, p):
    eps = model["rms_norm_eps"]
    x = _held(num, x + mixer(
        model, num, _norm(num, x, p["input_norm"]["scale"], eps), p["mixer"]))
    return _held(num, x + sparse_ffn(
        model, num, _norm(num, x, p["post_attn_norm"]["scale"], eps), p["mlp"]))


def hidden(model, params, tokens, num: Numerics):
    x = _held(num, params["embed_tokens"]["embedding"][tokens])
    linear = jax.checkpoint(
        lambda x, p: (_layer(model, num, gated_delta_net, x, p), None))
    full = jax.checkpoint(lambda x, p: _layer(model, num, gated_attention, x, p))

    def period(x, p):
        x, _ = jax.lax.scan(linear, x, p["linear"])
        return full(x, p["full"]), None

    x, _ = jax.lax.scan(period, x, params["periods"])
    return _norm(num, x, params["final_norm"]["scale"], model["rms_norm_eps"])


def loss(model, job, params, batch, num: Numerics = Numerics()):
    """Mean next-token cross-entropy over the B x (S-1) predicted positions
    and the slice of the vocabulary held here, the logits in blocks."""
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
