"""Attention numerics — the reference implementation every kernel is
tested against.

The reference never owned attention math (it launched MXNet/TF scripts);
BASELINE configs 3-4 (BERT, Llama) make it the hot op here. This module is
the straightforward XLA path: one batched matmul pair the MXU loves, fp32
softmax for bf16 stability. The Pallas flash/ring kernels in
:mod:`tpucfn.kernels` must match it to tolerance (SURVEY.md §7.4 item 3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """GQA: expand KV heads to match query heads. (B, S, Hkv, D) -> (B, S, Hkv*n_rep, D)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


NEG_INF = -1e30  # finite mask value: keeps max/exp nan-free for empty rows


def dot_product_attention_with_lse(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    mask: jax.Array | None = None,  # broadcastable to (B, Hq, Sq, Sk); True = attend
    q_offset: int | jax.Array = 0,  # global position of q[0] (ring/SP shards)
    k_offset: int | jax.Array = 0,
    scale: float | None = None,  # of the scores; None = the keys' D ** -0.5
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B,Sq,Hq,D), lse (B,Sq,Hq)). Softmax in fp32.

    The log-sum-exp output is what lets ring attention merge per-hop
    partial results exactly (online-softmax combining); rows that attend
    to nothing yield out = 0 and lse = NEG_INF.
    """
    orig_dtype = q.dtype
    hq, hkv = q.shape[2], k.shape[2]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)

    if scale is None:
        scale = q.shape[-1] ** -0.5
    # (B, H, Sq, Sk)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale

    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(sk)[None, :] + k_offset
        logits = jnp.where((qpos >= kpos)[None, None], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)

    m = jnp.max(logits, axis=-1)  # (B, H, Sq); NEG_INF for empty rows
    probs = jnp.where(logits > NEG_INF / 2, jnp.exp(logits - m[..., None]), 0.0)
    l = jnp.sum(probs, axis=-1)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1.0e-30).transpose(0, 2, 1)[..., None]
    out = jnp.where((l > 0).transpose(0, 2, 1)[..., None], out, 0.0)
    return out.astype(orig_dtype), lse.transpose(0, 2, 1)  # lse -> (B, Sq, Hq)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    mask: jax.Array | None = None,
    q_offset: int | jax.Array = 0,
    k_offset: int | jax.Array = 0,
    scale: float | None = None,
) -> jax.Array:
    """Returns (B, Sq, Hq, D); see :func:`dot_product_attention_with_lse`."""
    out, _ = dot_product_attention_with_lse(
        q, k, v, causal=causal, mask=mask, q_offset=q_offset, k_offset=k_offset,
        scale=scale,
    )
    return out
