"""One chip's share of dots3-note-prev (288B-A17B, ``model_type``
``dots3_note``) as its published configuration describes it: forward and loss
in plain float32, no kernel, nothing of ``tpucfn``.

With ``x`` a token's hidden state, ``N`` the RMS norm ``x * rsqrt(mean(x^2) +
eps) * w`` and ``x̂ = N(x)``:

- Block: ``h = x + Attn(x̂); y = h + FFN(N(h))``.
- Latent attention, two kinds with widths of their own (``swa_*`` for the
  window layers): ``c_q = N(x̂ W_qa)``; ``[q_nope | q_rot] = c_q W_qb`` a
  head; ``[c_kv | k_r] = x̂ W_kva``; ``[k_nope | v] = N(c_kv) W_kvb`` a head;
  ``R`` turns adjacent pairs of the rotary parts (the layer kind's theta);
  softmax of ``q k^T / sqrt(qk_nope + qk_rope)`` over the pairs the layer
  keeps; each head's output times ``sigmoid(x̂ W_g)`` of its own (headwise
  gate); ``W_o``.
- A window layer keeps ``0 <= t - s < sliding_window_size``.
- A full layer keeps the keys its indexer chose: ``qi = R(c_q W_iq)``
  (``index_n_heads`` of ``index_head_dim``, rotary on the first
  ``index_rope_head_dim``), ``ki = R(LayerNorm(x̂ W_ik))`` (eps 1e-6, a scale
  and a bias), ``w = x̂ W_iw / sqrt(index_n_heads)``, ``I[t, s] = Σ_j w_j
  ReLU(qi_j · ki_s) / sqrt(index_head_dim)``; ``S_t`` the ``index_topk``
  causal keys of largest ``I[t, ·]`` (``lax.top_k``: ties to the lower
  position), or every causal key while there are fewer.  ``x̂`` and ``c_q``
  enter the indexer detached.
- Its loss: ``KL(p_t ‖ softmax(I[t, S_t]))`` with ``p_t`` the attention's
  probabilities over ``S_t`` averaged over the heads held, detached; the mean
  over positions, times ``index_kl_weight``, for each full layer.
- Feed-forward: ``benchmark/reference/joyai_llm_flash.py``'s (a SwiGLU in the
  leading layers; then the sigmoid router with its selection bias over
  ``router_experts``, this chip's ``n_routed_experts`` held, the shared expert
  whole and ungated).
- After the last block the final norm and the untied head;
  ``loss = CE(next) + Σ_full KL``.

Where a batch carries ``dsa_words`` (the program's choice at that step, one
array a full layer in order, as ``benchmark/drivers/train_dsa.py`` hands it
on: bit ``j`` of lane ``l`` in word-row ``g`` of a query's row is key ``4096 g
+ 128 j + l``) the full layers attend over those keys: bfloat16 scores move
keys across a row's ``index_topk``-th place, and what is compared is the
arithmetic on one choice.  :func:`selection_check` compares the two choices.

Departures, all in the configuration's ``assumed``: no multi-token prediction
block and no vision or audio tower; ``apply_mla_qkv_lora_rescale`` taken as a
factor of 1; the indexer without its Hadamard rotation (orthogonal on both
sides) and without fp8; the sparse training stage's loss only (no dense
warm-up); the experts' gate and up projections are leaves of their own; the
selection bias stays where the seed put it; no auxiliary balance loss.

The control (``Numerics("fp8")``) rounds the operands of every product and
every tensor the program holds in bfloat16, as the other references do.

To fit one chip in float32 a block's activations are recomputed in the
backward pass, and scores are made a block of queries at a time; none of it
changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.joyai_llm_flash import (_held, _norm, _swiglu_spec,
                                                 _turn, cross_entropy,
                                                 dense_ffn, sparse_ffn)
from benchmark.reference.numerics import Numerics

QUERY_BLOCK = 64          # rows of a full layer's scores at a time
WINDOW_BLOCK = 256        # rows of a window layer's
GROUP, LANES, WORD = 4096, 128, 32


def widths(model, kind: str) -> dict:
    p = "" if kind == "full" else "swa_"
    return {"heads": model[f"{p}num_attention_heads"],
            "q_rank": model[f"{p}q_lora_rank"],
            "kv_rank": model[f"{p}kv_lora_rank"],
            "nope": model[f"{p}qk_nope_head_dim"],
            "rot": model[f"{p}qk_rope_head_dim"],
            "v": model[f"{p}v_head_dim"],
            "theta": model[f"{p}rope_theta"]}


def kinds(model) -> list[str]:
    return ["full" if t == "full_attention" else "window"
            for t in model["layer_types"]]


def layout(model) -> tuple[int, int]:
    """(periods, window layers a period) after the leading dense layers,
    whose kinds must be full, in whole periods of one full layer and then
    window layers."""
    lead = model["first_k_dense_replace"]
    k = kinds(model)
    rest = k[lead:]
    n = rest[1:].index("full") + 1 if "full" in rest[1:] else len(rest)
    unit = ["full"] + ["window"] * (n - 1)
    if (set(k[:lead]) != {"full"} or len(k) != model["num_hidden_layers"]
            or rest != unit * (len(rest) // n)):
        raise ValueError(f"layer kinds {k} are not full leading layers and "
                         "whole periods")
    return len(rest) // n, n - 1


def _attention_spec(model, kind, lead=()):
    d, std = model["hidden_size"], model["initializer_range"]
    wd = widths(model, kind)
    h = wd["heads"]
    spec = {
        "input_norm/scale": (lead + (d,), 1.0, 0.0),
        "mixer/q_a_proj/kernel": (lead + (d, wd["q_rank"]), 0.0, std),
        "mixer/q_a_norm/scale": (lead + (wd["q_rank"],), 1.0, 0.0),
        "mixer/q_b_proj/kernel": (lead + (wd["q_rank"],
                                          h * (wd["nope"] + wd["rot"])), 0.0, std),
        "mixer/kv_a_proj/kernel": (lead + (d, wd["kv_rank"] + wd["rot"]), 0.0, std),
        "mixer/kv_a_norm/scale": (lead + (wd["kv_rank"],), 1.0, 0.0),
        "mixer/kv_b_proj/kernel": (lead + (wd["kv_rank"],
                                           h * (wd["nope"] + wd["v"])), 0.0, std),
        "mixer/gate_proj/kernel": (lead + (d, h), 0.0, std),
        "mixer/o_proj/kernel": (lead + (h * wd["v"], d), 0.0, std),
        "post_attn_norm/scale": (lead + (d,), 1.0, 0.0),
    }
    if kind == "full":
        hi, di = model["index_n_heads"], model["index_head_dim"]
        spec.update({
            "indexer/wq_b/kernel": (lead + (wd["q_rank"], hi * di), 0.0, std),
            "indexer/wk/kernel": (lead + (d, di), 0.0, std),
            "indexer/k_norm/scale": (lead + (di,), 1.0, 0.0),
            "indexer/k_norm/bias": (lead + (di,), 0.0, 0.0),
            "indexer/weights_proj/kernel": (lead + (d, hi), 0.0, std),
        })
    return spec


def _sparse_spec(model, kind, lead=()):
    d, std = model["hidden_size"], model["initializer_range"]
    f = model["moe_intermediate_size"]
    return {
        **_attention_spec(model, kind, lead),
        "mlp/router/kernel": (lead + (d, model["router_experts"]), 0.0, std),
        "mlp/e_score_correction_bias": (lead + (model["router_experts"],), 0.0,
                                        model["selection_bias_std"]),
        **_swiglu_spec("mlp/experts", d, f, std,
                       lead + (model["n_routed_experts"],)),
        **_swiglu_spec("mlp/shared_expert", d,
                       f * model["n_shared_experts"], std, lead),
    }


def param_spec(model) -> dict:
    d, std, v = model["hidden_size"], model["initializer_range"], model["vocab_size"]
    periods, windows = layout(model)
    if model["moe_layer_freq"] != 1:
        raise ValueError("every layer after the leading ones is sparse")
    spec = {"embed_tokens/embedding": ((v, d), 0.0, std)}
    for i in range(model["first_k_dense_replace"]):
        spec.update({f"dense_{i}/{k}": s for k, s in {
            **_attention_spec(model, "full"),
            **_swiglu_spec("mlp", d, model["intermediate_size"], std)}.items()})
    spec.update({f"layers/full/{k}": s for k, s in
                 _sparse_spec(model, "full", (periods,)).items()})
    for i in range(windows):
        spec.update({f"layers/window_{i}/{k}": s for k, s in
                     _sparse_spec(model, "window", (periods,)).items()})
    spec.update({"final_norm/scale": ((d,), 1.0, 0.0),
                 "lm_head/kernel": ((d, v), 0.0, std)})
    return spec


def state_spec(model) -> dict:
    return {}


def _layer_norm(num: Numerics, x, p, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return _held(num, (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"])


def _latent(model, kind, num: Numerics, x, p):
    """(q, k, v, c_q) of one kind of latent attention; x is x̂."""
    b, s, _ = x.shape
    wd, eps = widths(model, kind), model["rms_norm_eps"]
    h, nope, rot, rkv = wd["heads"], wd["nope"], wd["rot"], wd["kv_rank"]
    proj = lambda t, name: _held(num, num.einsum(  # noqa: E731
        "bsd,de->bse", t, p[name]["kernel"]))
    c_q = _norm(num, proj(x, "q_a_proj"), p["q_a_norm"]["scale"], eps)
    q = proj(c_q, "q_b_proj").reshape(b, s, h, nope + rot)
    kva = proj(x, "kv_a_proj")
    c_kv = _norm(num, kva[..., :rkv], p["kv_a_norm"]["scale"], eps)
    k_r = _held(num, _turn(kva[..., rkv:].reshape(b, s, 1, rot), wd["theta"]))
    kv = proj(c_kv, "kv_b_proj").reshape(b, s, h, -1)
    q = jnp.concatenate(
        [q[..., :nope], _held(num, _turn(q[..., nope:], wd["theta"]))], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, h, rot))], axis=-1)
    return q, k, kv[..., nope:], c_q


def _gated_out(num: Numerics, x, a, p):
    """Each head's output times its gate, then ``W_o``; a (B, S, H, Dv)."""
    b, s, h, dv = a.shape
    g = jax.nn.sigmoid(num.einsum("bsd,dh->bsh", x, p["gate_proj"]["kernel"]))
    a = _held(num, a * _held(num, g)[..., None]).reshape(b, s, h * dv)
    return _held(num, num.einsum("bse,ed->bsd", a, p["o_proj"]["kernel"]))


def _window_attention(model, num: Numerics, q, k, v):
    """Softmax over the last ``sliding_window_size`` positions, a block of
    queries at a time against the keys the block can see."""
    b, s, h, d = q.shape
    win = model["sliding_window_size"]
    block = min(WINDOW_BLOCK, s)
    if s % block:
        raise ValueError(f"S {s} is no multiple of {block}")
    span = block + win - 1
    kp = jnp.pad(k, ((0, 0), (win - 1, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (win - 1, 0), (0, 0), (0, 0)))

    @jax.checkpoint
    def rows(lo):
        qi = jax.lax.dynamic_slice_in_dim(q, lo, block, 1)
        ki = jax.lax.dynamic_slice_in_dim(kp, lo, span, 1)
        vi = jax.lax.dynamic_slice_in_dim(vp, lo, span, 1)
        sc = num.einsum("bqhd,bkhd->bhqk", qi, ki) * d ** -0.5
        t = lo + jnp.arange(block)[:, None]
        pos = lo - (win - 1) + jnp.arange(span)[None, :]
        keep = (pos >= 0) & (t - pos >= 0) & (t - pos < win)
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return num.einsum("bhqk,bkhd->bqhd", pr, vi)

    out = jax.lax.map(rows, jnp.arange(0, s, block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def unpack_rows(words, lo, rows: int, s: int):
    """The chosen keys of rows ``lo .. lo + rows`` from the program's words
    (B, G, S, LANES) as (B, rows, S) bool."""
    w = jax.lax.dynamic_slice_in_dim(words, lo, rows, 2)          # (B, G, r, L)
    bits = (w[:, :, :, None, :] >> jnp.arange(WORD)[:, None]) & 1  # (B,G,r,32,L)
    b, g = w.shape[:2]
    return jnp.moveaxis(bits, 1, 2).reshape(b, rows, g * GROUP)[:, :, :s] != 0


def indexer(model, num: Numerics, x, c_q, p):
    """(qi (B, S, HI, DI), ki (B, S, DI), w (B, S, HI)) from detached x̂ and
    c_q, every constant factor in ``w``."""
    b, s, _ = x.shape
    hi, di = model["index_n_heads"], model["index_head_dim"]
    rot, theta = model["index_rope_head_dim"], model["rope_theta"]
    x, c_q = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)
    turn = lambda t: jnp.concatenate(  # noqa: E731
        [_turn(t[..., :rot], theta), t[..., rot:]], axis=-1)
    qi = _held(num, turn(_held(num, num.einsum(
        "bsr,re->bse", c_q, p["wq_b"]["kernel"])).reshape(b, s, hi, di)))
    ki = _layer_norm(num, _held(num, num.einsum("bsd,de->bse", x,
                                                p["wk"]["kernel"])), p["k_norm"])
    ki = _held(num, turn(ki.reshape(b, s, 1, di))[:, :, 0])
    w = _held(num, num.einsum("bsd,dh->bsh", x, p["weights_proj"]["kernel"])
              ) * (hi * di) ** -0.5
    return qi, ki, w


def _index_scores(num: Numerics, qi_rows, ki, w_rows, lo):
    """(B, rows, S) float32 ``I`` of rows ``lo ..``, ``-inf`` off causality."""
    a = num.einsum("bqhd,bsd->bhqs", qi_rows, ki)
    i = num.einsum("bhqs,bqh->bqs", jnp.maximum(a, 0.0), w_rows)
    rows, s = qi_rows.shape[1], ki.shape[1]
    causal = (lo + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
    return jnp.where(causal, i, -jnp.inf)


def _own_choice(i, topk: int):
    """(B, rows, S) bool: the ``topk`` largest causal scores of each row."""
    b, rows, s = i.shape
    _, idx = jax.lax.top_k(i, min(topk, s))
    mask = jnp.zeros((b, rows, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(rows)[None, :, None], idx].set(True)
    return mask & jnp.isfinite(i)


def _sparse_attention(model, num: Numerics, q, k, v, qi, ki, w, words):
    """(output (B, S, H, Dv), the sum over rows of the indexer's KL)."""
    b, s, h, d = q.shape
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"S {s} is no multiple of {block}")
    topk = model["index_topk"]

    @jax.checkpoint
    def rows(lo):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, lo, block, 1)  # noqa: E731
        i = _index_scores(num, sl(qi), ki, sl(w), lo)
        keep = (_own_choice(jax.lax.stop_gradient(i), topk) if words is None
                else unpack_rows(words, lo, block, s))
        sc = num.einsum("bqhd,bkhd->bhqk", sl(q), k) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(keep[:, None], sc, -jnp.inf), axis=-1)
        out = num.einsum("bhqk,bkhd->bqhd", pr, v)
        p = jax.lax.stop_gradient(jnp.mean(pr, axis=1))
        log_q = i - jax.nn.logsumexp(jnp.where(keep, i, -jnp.inf), axis=-1,
                                     keepdims=True)
        safe = jnp.where(p > 0, p, 1.0)
        kl = jnp.sum(jnp.where(keep & (p > 0), p * (jnp.log(safe) - log_q), 0.0))
        return out, kl

    out, kl = jax.lax.map(rows, jnp.arange(0, s, block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1]), jnp.sum(kl)


def _block(model, num: Numerics, kind, ffn, x, p, words):
    """(y, the layer's mean KL, or 0 for a window layer)."""
    eps = model["rms_norm_eps"]
    xn = _norm(num, x, p["input_norm"]["scale"], eps)
    q, k, v, c_q = _latent(model, kind, num, xn, p["mixer"])
    kl = jnp.zeros((), jnp.float32)
    if kind == "full":
        qi, ki, w = indexer(model, num, xn, c_q, p["indexer"])
        a, kl = _sparse_attention(model, num, q, k, v, qi, ki, w, words)
        kl = kl / (x.shape[0] * x.shape[1])
    else:
        a = _window_attention(model, num, q, k, v)
    x = _held(num, x + _gated_out(num, xn, _held(num, a), p["mixer"]))
    y = _held(num, x + ffn(model, num, _norm(num, x, p["post_attn_norm"]["scale"],
                                              eps), p["mlp"]))
    return y, kl


def hidden(model, params, tokens, num: Numerics, words=None):
    """(the final normed hidden states, Σ over full layers of their KL).
    ``words``: the program's choice, (full layers, B, G, S, LANES), or None
    for the reference's own."""
    eps = model["rms_norm_eps"]
    periods, _ = layout(model)
    lead = model["first_k_dense_replace"]
    pick = (lambda j: None) if words is None else (lambda j: words[j])  # noqa: E731
    layer = {kind: {ffn.__name__: jax.checkpoint(
        lambda x, p, wd, kind=kind, ffn=ffn: _block(model, num, kind, ffn, x, p, wd))
        for ffn in (dense_ffn, sparse_ffn)} for kind in ("full", "window")}
    x = _held(num, params["embed_tokens"]["embedding"][tokens])
    total = jnp.zeros((), jnp.float32)
    for i in range(lead):
        x, kl = layer["full"]["dense_ffn"](x, params[f"dense_{i}"], pick(i))
        total = total + kl
    _, windows = layout(model)
    for per in range(periods):
        at = lambda name, per=per: jax.tree.map(  # noqa: E731
            lambda a: a[per], params["layers"][name])
        x, kl = layer["full"]["sparse_ffn"](x, at("full"), pick(lead + per))
        total = total + kl
        for i in range(windows):
            x, _ = layer["window"]["sparse_ffn"](x, at(f"window_{i}"), None)
    return _norm(num, x, params["final_norm"]["scale"], eps), total


def loss(model, job, params, batch, num: Numerics = Numerics()):
    tokens = batch["tokens"]
    h, kl = hidden(model, params, tokens, num, batch.get("dsa_words"))
    return (cross_entropy(num, h, params["lm_head"]["kernel"], tokens, 1)
            + model["index_kl_weight"] * kl)


def selection_check(model, margin: float, control: Numerics | None = None):
    """``(params, tokens, words) -> (count, worst)``: where the program's
    choice ``words`` (full layers, B, G, S, LANES) and the reference's own
    differ, at the reference's forward pass on ``params``: the keys that
    differ and whose reference score lies more than ``margin`` row deviations
    from the row's ``index_topk``-th score, and the largest such distance of
    any key that differs.  Jitted once, for the steps a run compares.

    ``control``: the arithmetic of a choice that takes the program's place
    (``words`` None): the reference's own top-k over scores from the
    indexer's operands rounded to it, the control the margin is set against.
    """
    num = Numerics()
    eps, lead = model["rms_norm_eps"], model["first_k_dense_replace"]
    periods, windows = layout(model)
    topk = model["index_topk"]

    def probe(x, p, wd):
        xn = _norm(num, x, p["input_norm"]["scale"], eps)
        _, _, _, c_q = _latent(model, "full", num, xn, p["mixer"])
        qi, ki, w = indexer(model, num, xn, c_q, p["indexer"])
        if wd is None:
            qc, kc, wc = indexer(model, control, xn, c_q, p["indexer"])
        b, s = x.shape[:2]
        block = min(QUERY_BLOCK, s)

        def rows(lo):
            sl = lambda t: jax.lax.dynamic_slice_in_dim(t, lo, block, 1)  # noqa: E731
            i = _index_scores(num, sl(qi), ki, sl(w), lo)
            ours = _own_choice(i, topk)
            theirs = (unpack_rows(wd, lo, block, s) if wd is not None else
                      _own_choice(_index_scores(control, sl(qc), kc, sl(wc), lo),
                                  topk))
            live = jnp.isfinite(i)
            n = jnp.sum(live, axis=-1, keepdims=True)
            mean = jnp.sum(jnp.where(live, i, 0.0), -1, keepdims=True) / n
            dev = jnp.sqrt(jnp.sum(jnp.where(live, (i - mean) ** 2, 0.0), -1,
                                   keepdims=True) / n)
            kth = jax.lax.top_k(i, min(topk, s))[0][..., -1:]
            dist = jnp.abs(i - kth) / jnp.maximum(dev, 1e-30)
            differ = (ours != theirs) & live
            return (jnp.sum(differ & (dist > margin)),
                    jnp.max(jnp.where(differ, dist, 0.0)))

        counts, worst = jax.lax.map(rows, jnp.arange(0, s, block))
        return jnp.sum(counts), jnp.max(worst)

    @jax.jit
    def run(params, tokens, words):
        pick = (lambda j: None) if words is None else (lambda j: words[j])  # noqa: E731
        x = params["embed_tokens"]["embedding"][tokens]
        found = []
        for i in range(lead):
            p = params[f"dense_{i}"]
            found.append(probe(x, p, pick(i)))
            x, _ = _block(model, num, "full", dense_ffn, x, p, pick(i))
        for per in range(periods):
            at = lambda name: jax.tree.map(  # noqa: E731
                lambda a: a[per], params["layers"][name])
            found.append(probe(x, at("full"), pick(lead + per)))
            x, _ = _block(model, num, "full", sparse_ffn, x, at("full"),
                          pick(lead + per))
            for i in range(windows):
                x, _ = _block(model, num, "window", sparse_ffn, x,
                              at(f"window_{i}"), None)
        return (sum(c for c, _ in found),
                jnp.max(jnp.stack([m for _, m in found])))

    def check(params, tokens, words=None):
        if (words is None) == (control is None):
            raise ValueError("the program's words, or a control, not both")
        with jax.default_matmul_precision("highest"):
            count, worst = run(params, jnp.asarray(tokens),
                               None if words is None else jnp.asarray(words))
        return int(count), float(worst)

    return check
