"""Operations and bytes of one chip's share of dots3-note-prev, as functions
of its shapes, in the manner of ``benchmark/flops.py``: what the forward and
backward passes need, a multiply-add two operations, a backward pass two
forward passes' products, recomputation never counted, routed rows at their
expectation (``num_experts_per_tok * n_routed_experts / router_experts`` a
token).

The rule as written: the indexer scores every causal pair (its ``index_n_heads
x index_head_dim`` products and one weighted sum a head), attention runs over
the chosen pairs (``min(index_topk, t + 1)`` keys of query ``t``) and a
window layer over ``min(sliding_window_size, t + 1)``.  The indexer learns
from its KL loss alone and its inputs are detached: its projections' backward
makes the weights' gradient only (one product), and its scores' backward is
over the chosen pairs, where the loss's gradient lives.  The KL's
probabilities are the attention's own, no product of their own."""

from __future__ import annotations

from benchmark.flops_joyai_llm_flash import FLASH_PRODUCTS, dense_ffn_macs


def chosen_pairs(seq: int, topk: int) -> float:
    """Σ_t min(topk, t + 1): pairs a query row keeps of its causal keys."""
    k = min(topk, seq)
    return k * (k + 1) / 2 + (seq - k) * k


def causal_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2


def _heads(m: dict, kind: str) -> dict:
    p = "" if kind == "full" else "swa_"
    return {"h": m[f"{p}num_attention_heads"], "rq": m[f"{p}q_lora_rank"],
            "rkv": m[f"{p}kv_lora_rank"], "nope": m[f"{p}qk_nope_head_dim"],
            "rot": m[f"{p}qk_rope_head_dim"], "v": m[f"{p}v_head_dim"]}


def attention_macs(m: dict, kind: str) -> int:
    """A token's multiply-adds in one latent attention outside the scores:
    both down-projections, both up-projections, the gate and the output."""
    d, a = m["hidden_size"], _heads(m, kind)
    return (d * a["rq"] + a["rq"] * a["h"] * (a["nope"] + a["rot"])
            + d * (a["rkv"] + a["rot"]) + a["rkv"] * a["h"] * (a["nope"] + a["v"])
            + d * a["h"] + a["h"] * a["v"] * d)


def indexer_macs(m: dict) -> int:
    """A token's multiply-adds in the indexer's projections."""
    hi, di = m["index_n_heads"], m["index_head_dim"]
    return m["q_lora_rank"] * hi * di + m["hidden_size"] * (di + hi)


def index_pair_flops(m: dict) -> float:
    """One pair's indexer score: the heads' products and their weighted sum."""
    return 2.0 * m["index_n_heads"] * (m["index_head_dim"] + 1)


def sparse_ffn_macs(m: dict) -> float:
    from benchmark.flops_joyai_llm_flash import expert_macs

    return (m["hidden_size"] * m["router_experts"]
            + m["n_shared_experts"] * expert_macs(m)
            + m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["router_experts"] * expert_macs(m))


def _kinds(m: dict) -> tuple[int, int, int]:
    """(full layers, window layers, leading dense layers)."""
    t = m["layer_types"]
    return (t.count("full_attention"), t.count("sliding_attention"),
            m["first_k_dense_replace"])


def attend_call(kind: str, m: dict, batch: int, seq: int,
                itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) one call of a flash kernel (``fwd``, ``dkv``,
    ``dq``) needs over the pairs a layer keeps: the chosen pairs of a full
    layer (``dsa_*``) or the window's (``swa_*``, ``kind`` prefixed
    ``swa_``)."""
    window = kind.startswith("swa_")
    part = kind.removeprefix("swa_")
    a = _heads(m, "window" if window else "full")
    pairs = (chosen_pairs(seq, m["sliding_window_size"]) if window
             else chosen_pairs(seq, m["index_topk"]))
    n_qk, n_v = FLASH_PRODUCTS[part]
    qk = a["nope"] + a["rot"]
    ops = (n_qk * qk + n_v * a["v"]) * 2.0 * pairs * a["h"] * batch
    q = batch * seq * a["h"] * qk * itemsize
    o = batch * seq * a["h"] * a["v"] * itemsize
    lse = batch * seq * a["h"] * 4
    moved = {"fwd": 2 * q + 2 * o + lse,
             "dkv": q + 2 * o + 2 * q + 2 * o + 2 * lse,
             "dq": 2 * q + 2 * o + q + o + 2 * lse}[part]
    if not window:   # the selection's words, read once a call
        moved += batch * seq * seq / 8
    return ops, float(moved)


def index_step(m: dict, batch: int, seq: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) a step's ``dsa_select`` calls need: each full
    layer scores its causal pairs once (the choice is saved across the
    rematerialised pass) and writes the words and one log-sum-exp a row."""
    full, _, _ = _kinds(m)
    hi, di = m["index_n_heads"], m["index_head_dim"]
    ops = full * batch * causal_pairs(seq) * index_pair_flops(m)
    moved = full * batch * (seq * hi * di * itemsize + seq * di * itemsize
                            + seq * hi * 4 + seq * seq / 8 + seq * 4)
    return ops, float(moved)


def attend_step(m: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) a step's attention over the chosen keys needs:
    each full layer's three flash calls over the chosen pairs, and the KL
    loss's: the indexer's scores on the chosen pairs for its value and both
    its gradients (the probabilities are the attention's own)."""
    full, _, _ = _kinds(m)
    ops = moved = 0.0
    for part in FLASH_PRODUCTS:
        o, b = attend_call(part, m, batch, seq)
        ops, moved = ops + full * o, moved + full * b
    kl = 3 * batch * chosen_pairs(seq, m["index_topk"]) * index_pair_flops(m)
    return ops + full * kl, moved


def swa_step(m: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) a step's window attention needs: each window
    layer's three flash calls over the window's pairs."""
    _, window, _ = _kinds(m)
    ops = moved = 0.0
    for part in FLASH_PRODUCTS:
        o, b = attend_call("swa_" + part, m, batch, seq)
        ops, moved = ops + window * o, moved + window * b
    return ops, moved


def forward_flops(m: dict, batch: int, seq: int) -> float:
    """The trunk's forward pass and the head's."""
    full, window, dense = _kinds(m)
    d = m["hidden_size"]
    per_token = (full * (attention_macs(m, "full") + indexer_macs(m))
                 + window * attention_macs(m, "window")
                 + dense * dense_ffn_macs(m)
                 + (full + window - dense) * sparse_ffn_macs(m)
                 + d * m["vocab_size"])
    a, w = _heads(m, "full"), _heads(m, "window")
    scores = (full * 2.0 * a["h"] * (a["nope"] + a["rot"] + a["v"])
              * chosen_pairs(seq, m["index_topk"])
              + window * 2.0 * w["h"] * (w["nope"] + w["rot"] + w["v"])
              * chosen_pairs(seq, m["sliding_window_size"])
              + full * index_pair_flops(m) * causal_pairs(seq))
    return batch * (seq * 2.0 * per_token + scores)


def step_flops(m: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: three times the forward, but the
    indexer's: its projections twice (forward, weights' gradient) and its
    scores once over the causal pairs and twice over the chosen ones."""
    full, _, _ = _kinds(m)
    index_proj = full * batch * seq * 2.0 * indexer_macs(m)
    index_scores = full * batch * index_pair_flops(m) * causal_pairs(seq)
    index_back = full * batch * 2 * index_pair_flops(m) * chosen_pairs(
        seq, m["index_topk"])
    return (3 * (forward_flops(m, batch, seq) - index_proj - index_scores)
            + 2 * index_proj + index_scores + index_back)
