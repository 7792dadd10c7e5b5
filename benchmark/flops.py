"""Operations and bytes an algorithm needs, as functions of its shapes, and
the table of peaks.  Never XLA's cost analysis (it counts a scanned layer
once) and never recomputed operations: what the forward and backward passes
require.  A multiply-add is two operations; a backward pass costs two forward
passes' products."""

from __future__ import annotations

import json
from pathlib import Path


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add its published peaks, do not guess")
    return table[device_kind]


def _same(size: int, stride: int) -> int:
    return -(-size // stride)


def resnet_forward_macs(model: dict) -> int:
    """Multiply-adds of one image's forward pass (convolutions and head)."""
    hw = _same(model["image_size"], 2)                  # 7x7/2 stem
    macs = hw * hw * 7 * 7 * 3 * model["width"]
    hw = _same(hw, 2)                                   # 3x3/2 max pool
    cin = model["width"]
    for stage, n in enumerate(model["stage_sizes"]):
        f = model["width"] * 2 ** stage
        for b in range(n):
            stride = 2 if stage > 0 and b == 0 else 1
            out = _same(hw, stride)
            macs += hw * hw * cin * f                   # 1x1
            macs += out * out * 9 * f * f               # 3x3, strided
            macs += out * out * f * 4 * f               # 1x1
            if cin != 4 * f or stride != 1:
                macs += out * out * cin * 4 * f         # projection
            hw, cin = out, 4 * f
    return macs + cin * model["num_classes"]


def decoder_layer_matmul_params(model: dict) -> int:
    d, hd = model["hidden_size"], model["head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return 2 * d * nq * hd + 2 * d * nkv * hd + 3 * d * model["intermediate_size"]


def attention_forward_flops(seq: int, heads: int, head_dim: int,
                            causal: bool = True) -> float:
    """One sequence, one layer: QK^T and PV; causal counts the half that is
    not masked."""
    full = 2 * 2.0 * seq * seq * head_dim * heads
    return full / 2 if causal else full


def decoder_forward_flops(model: dict, batch: int, seq: int) -> float:
    per_token = 2.0 * (model["num_hidden_layers"] * decoder_layer_matmul_params(model)
                       + model["hidden_size"] * model["vocab_size"])
    attn = model["num_hidden_layers"] * attention_forward_flops(
        seq, model["num_attention_heads"], model["head_dim"])
    return batch * (seq * per_token + attn)


def train_step_flops(config: dict, shape: dict) -> float:
    """Forward and backward of one optimizer step at the cell's shape, as the
    configuration's family counts them."""
    from benchmark import families

    return families.load(config["family"]).step_flops(config["model"], shape)


# Products of (S x S x head_dim) size per head that each flash kernel makes:
# forward QK^T, PV; the key/value backward recomputes QK^T and makes dV, dP,
# dK; the query backward recomputes QK^T and makes dP, dQ.
FLASH_PRODUCTS = {"fwd": 2, "dkv": 4, "dq": 3}


def flash_call(kind: str, batch: int, seq: int, heads: int, kv_heads: int,
               head_dim: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) one call of a causal flash kernel needs."""
    ops = FLASH_PRODUCTS[kind] * 2.0 * seq * seq * head_dim * heads * batch / 2
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    lse = batch * seq * heads * 4
    moved = {"fwd": 2 * q + 2 * kv + lse,            # q,k,v in; o,lse out
             "dkv": 3 * q + 4 * kv + 2 * lse,        # q,k,v,o,do,lse,delta; dk,dv
             "dq": 4 * q + 2 * kv + 2 * lse}[kind]   # the same in; dq out
    return ops, float(moved)


def roofline_seconds(ops: float, moved: float, peak: dict) -> tuple[float, str]:
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = moved / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
