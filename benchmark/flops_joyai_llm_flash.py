"""Operations and bytes of one chip's share of JoyAI-LLM-Flash, as functions
of its shapes, in the manner of ``benchmark/flops.py``: what the forward and
backward passes need, a multiply-add two operations, a backward pass two
forward passes' products, recomputation never counted, routed rows at their
expectation (``num_experts_per_tok * n_routed_experts / router_experts`` a
token), attention causal.  A head's keys are ``qk_head_dim`` wide and its
values ``v_head_dim``: the counts are of those sizes, never of a padded one,
so a kernel that pads its products reads low against them."""

from __future__ import annotations


def attention_macs(m: dict) -> int:
    """Multiply-adds a token in one latent attention outside the scores: both
    down-projections, both up-projections and the output projection."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * h * m["qk_head_dim"]
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def expert_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_ffn_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def sparse_ffn_macs(m: dict) -> float:
    """Router, shared expert, and the routed rows expected on the experts
    held here."""
    fixed = (m["hidden_size"] * m["router_experts"]
             + m["n_shared_experts"] * expert_macs(m))
    routed = (m["num_experts_per_tok"] * m["n_routed_experts"]
              / m["router_experts"] * expert_macs(m))
    return fixed + routed


def attention_score_flops(m: dict, seq: int) -> float:
    """One sequence, one block: q k^T over ``qk_head_dim`` and p v over
    ``v_head_dim``, the half that causality keeps."""
    return (float(seq) * seq * m["num_attention_heads"]
            * (m["qk_head_dim"] + m["v_head_dim"]))


def forward_flops(m: dict, batch: int, seq: int) -> float:
    """The trunk (leading dense layers, then sparse ones), the prediction
    blocks after it (each a sparse block behind a ``2 d x d`` projection) and
    one pass of the head for the trunk and one a prediction block."""
    dense = m["first_k_dense_replace"]
    sparse = m["num_hidden_layers"] - dense
    extra = m["num_nextn_predict_layers"]
    d = m["hidden_size"]
    blocks = dense + sparse + extra
    per_token = (blocks * attention_macs(m) + dense * dense_ffn_macs(m)
                 + (sparse + extra) * sparse_ffn_macs(m)
                 + (1 + extra) * d * m["vocab_size"] + extra * 2 * d * d)
    return batch * (seq * 2.0 * per_token
                    + blocks * attention_score_flops(m, seq))


# Products of (S x S) size per head that each flash kernel makes, by the head
# size they run over: forward q k^T (keys' size) and p v (values'); the
# key/value backward recomputes q k^T and makes dv (values'), dp (values') and
# dk (keys'); the query backward recomputes q k^T and makes dp and dq.
FLASH_PRODUCTS = {"fwd": (1, 1), "dkv": (2, 2), "dq": (2, 1)}


def flash_call(kind: str, batch: int, seq: int, heads: int, kv_heads: int,
               qk_dim: int, v_dim: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) one call of a causal flash kernel needs when keys
    and values differ in head size; equal sizes give ``flops.flash_call``."""
    n_qk, n_v = FLASH_PRODUCTS[kind]
    ops = (n_qk * qk_dim + n_v * v_dim) * 2.0 * seq * seq * heads * batch / 2
    q = batch * seq * heads * qk_dim * itemsize
    o = batch * seq * heads * v_dim * itemsize
    k = batch * seq * kv_heads * qk_dim * itemsize
    v = batch * seq * kv_heads * v_dim * itemsize
    lse = batch * seq * heads * 4
    moved = {"fwd": q + k + v + o + lse,                     # q,k,v in; o,lse out
             "dkv": q + 2 * o + 2 * k + 2 * v + 2 * lse,     # q,k,v,o,do,lse,delta; dk,dv
             "dq": 2 * q + 2 * o + k + v + 2 * lse}[kind]    # the same in; dq out
    return ops, float(moved)


def grouped_product(rows: float, m: dict, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one grouped matrix product of the expert layer
    over ``rows`` assignment rows: forward, the gradient of the rows and the
    gradient of the weights all multiply rows x hidden x expert width, and
    move the rows on both sides and every held expert's matrix once."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    ops = 2.0 * rows * d * f
    moved = itemsize * (rows * (d + f) + m["n_routed_experts"] * d * f)
    return ops, float(moved)
