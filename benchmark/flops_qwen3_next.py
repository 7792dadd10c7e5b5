"""Operations and bytes of one chip's share of Qwen3-Next-80B-A3B, as
functions of its shapes, in the manner of ``benchmark/flops.py``: what the
forward and backward passes need, a multiply-add two operations, a backward
pass two forward passes' products, recomputation never counted, routed rows at
their expectation (``top_k * held / router_experts`` a token)."""

from __future__ import annotations

from benchmark import flops


def delta_layer_macs(m: dict) -> int:
    """Multiply-adds a token in one Gated DeltaNet mixer outside the
    recurrence: q, k, v, z, b, a and output projections, and the depthwise
    convolution."""
    d = m["hidden_size"]
    key = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    val = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    proj = d * (2 * key + 2 * val + 2 * m["linear_num_value_heads"]) + val * d
    return proj + m["linear_conv_kernel_dim"] * (2 * key + val)


def delta_rule_macs(m: dict) -> int:
    """A token's state products in the recurrence as written: S^T k, k u^T
    and S^T q, each key dim x value dim, a value head."""
    return (3 * m["linear_num_value_heads"] * m["linear_key_head_dim"]
            * m["linear_value_head_dim"])


def attention_layer_macs(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * nq * 2 * hd + 2 * d * nkv * hd + nq * hd * d


def expert_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def ffn_macs(m: dict) -> float:
    """Router, shared expert and its gate, and the routed rows expected on
    the experts held here."""
    d = m["hidden_size"]
    fixed = d * m["router_experts"] + 3 * d * m["shared_expert_intermediate_size"] + d
    routed = (m["num_experts_per_tok"] * m["num_experts"] / m["router_experts"]
              * expert_macs(m))
    return fixed + routed


def forward_flops(m: dict, batch: int, seq: int) -> float:
    interval = m["full_attention_interval"]
    periods = m["num_hidden_layers"] // interval
    per_token = periods * ((interval - 1) * (delta_layer_macs(m) + delta_rule_macs(m))
                           + attention_layer_macs(m) + interval * ffn_macs(m))
    per_token += m["hidden_size"] * m["vocab_size"]
    attn = periods * flops.attention_forward_flops(
        seq, m["num_attention_heads"], m["head_dim"])
    return batch * (seq * 2.0 * per_token + attn)


def grouped_product(rows: float, m: dict, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one grouped matrix product of the expert layer
    over ``rows`` assignment rows: forward, the gradient of the rows and the
    gradient of the weights all multiply rows x hidden x expert width, and
    move the rows on both sides and every held expert's matrix once."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    ops = 2.0 * rows * d * f
    moved = itemsize * (rows * (d + f) + m["num_experts"] * d * f)
    return ops, float(moved)
