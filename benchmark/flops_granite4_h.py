"""Operations and bytes of Granite-4.0-H-Micro's layers, as functions of its
shapes, in the manner of ``benchmark/flops.py``: what the forward and backward
passes need, a multiply-add two operations, a backward pass two forward
passes' products, recomputation never counted, attention causal.  The
state-space recurrence is counted as its chunked form at ``mamba_chunk_size``
makes it (the form every implementation of it on a matrix unit has), **whatever
implements it**: a later kernel changes the time, not the count."""

from __future__ import annotations

from benchmark import flops


def _mamba(m: dict) -> tuple[int, int, int, int]:
    return (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
            m["mamba_n_groups"])


def mamba_layer_macs(m: dict) -> int:
    """Multiply-adds a token in one Mamba-2 mixer outside the recurrence:
    ``in_proj`` (z, x, B, C, dt), ``out_proj`` and the depthwise convolution."""
    heads, head, state, groups = _mamba(m)
    inner = heads * head
    conv = inner + 2 * groups * state
    return (m["hidden_size"] * (inner + conv + heads) + inner * m["hidden_size"]
            + m["mamba_d_conv"] * conv)


def recurrence_macs(m: dict) -> int:
    """A token's multiply-adds in the recurrence, chunked at ``L =
    mamba_chunk_size``: a group's ``C_i . B_j`` against the chunk's ``L``
    positions; a head's chunk-local sum over them; the state's part of the
    output; the token's own part of the next state."""
    heads, head, state, groups = _mamba(m)
    chunk = m["mamba_chunk_size"]
    return groups * chunk * state + heads * head * (chunk + 2 * state)


def attention_layer_macs(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return 2 * d * nq * hd + 2 * d * nkv * hd


def ffn_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["shared_intermediate_size"]


def forward_flops(m: dict, batch: int, seq: int) -> float:
    """Every layer's mixer and feed-forward, and one pass of the tied head."""
    kinds = m["layer_types"]
    mamba, attn = kinds.count("mamba"), kinds.count("attention")
    if mamba + attn != m["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    per_token = (mamba * (mamba_layer_macs(m) + recurrence_macs(m))
                 + attn * attention_layer_macs(m)
                 + (mamba + attn) * ffn_macs(m)
                 + m["hidden_size"] * m["vocab_size"])
    scores = attn * flops.attention_forward_flops(
        seq, m["num_attention_heads"], m["head_dim"])
    return batch * (seq * 2.0 * per_token + scores)


# how many times a pass makes the recurrence's products: the backward pass
# makes each of them for both of its operands
SSD_PASSES = {"fwd": 1, "bwd": 2}


def ssd_call(kind: str, batch: int, seq: int, m: dict,
             itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) one pass of one layer's recurrence needs: forward it
    reads x, B and C in the compute dtype and dt in float32 once and writes y
    and each chunk's float32 state once; backward it reads those and y's
    gradient and writes the four gradients."""
    heads, head, state, groups = _mamba(m)
    chunks = -(-seq // m["mamba_chunk_size"])
    ops = SSD_PASSES[kind] * 2.0 * recurrence_macs(m) * batch * seq
    x = batch * seq * heads * head * itemsize
    bc = 2 * batch * seq * groups * state * itemsize
    dt = batch * seq * heads * 4
    states = batch * chunks * heads * head * state * 4
    moved = {"fwd": x + bc + dt + x + states,            # x,B,C,dt in; y,states out
             "bwd": 2 * (x + bc + dt) + x + states}[kind]   # those, dy, states in; four gradients out
    return ops, float(moved)
