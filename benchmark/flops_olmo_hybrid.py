"""Operations and bytes of Olmo-Hybrid-7B's layers, as functions of its
shapes, in the manner of ``benchmark/flops.py``: what the forward and backward
passes need, a multiply-add two operations, a backward pass two forward
passes' products, recomputation never counted, attention causal.  Every count
is at the model's own head sizes (keys of 96, values of 192), never at the
width a kernel pads a head to."""

from __future__ import annotations

from benchmark import flops
from benchmark.flops_qwen3_next import delta_layer_macs, delta_rule_macs
from benchmark.reference.olmo_hybrid import layout


def attention_layer_macs(m: dict) -> int:
    """q, k, v and o projections a token (the QK-norm is no product)."""
    d, hd = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return 2 * d * nq * hd + 2 * d * nkv * hd


def ffn_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def forward_flops(m: dict, batch: int, seq: int) -> float:
    """Every layer's mixer and feed-forward and one pass of the head; the
    delta rule as written, its three state products a token and value head
    (``flops_qwen3_next.delta_rule_macs``: the same keys)."""
    periods, linear = layout(m)
    per_token = periods * (linear * (delta_layer_macs(m) + delta_rule_macs(m))
                           + attention_layer_macs(m) + (linear + 1) * ffn_macs(m))
    per_token += m["hidden_size"] * m["vocab_size"]
    scores = periods * flops.attention_forward_flops(
        seq, m["num_attention_heads"], m["head_dim"])
    return batch * (seq * 2.0 * per_token + scores)


# how many times a pass makes the rule's products: the backward pass makes
# each of them for both of its operands
GDN_PASSES = {"fwd": 1, "bwd": 2}


def gdn_call(kind: str, batch: int, seq: int, m: dict, chunk: int,
             itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) one pass of one layer's delta rule needs at least:
    the rule as written (three state products a token and value head);
    forward it reads q, k and v in the compute dtype and g and beta in float32
    once, and writes o and each chunk's float32 state once; backward it reads
    those, o's gradient and the states, and writes the five gradients."""
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    chunks = -(-seq // chunk)
    ops = GDN_PASSES[kind] * 2.0 * delta_rule_macs(m) * batch * seq
    qk = 2 * batch * seq * hk * dk * itemsize
    v = batch * seq * hv * dv * itemsize
    gb = 2 * batch * seq * hv * 4
    states = batch * chunks * hv * dk * dv * 4
    moved = {"fwd": qk + v + gb + v + states,            # q,k,v,g,beta in; o,states out
             "bwd": 2 * (qk + v + gb) + v + states}[kind]   # those, do, states in; five gradients out
    return ops, float(moved)
