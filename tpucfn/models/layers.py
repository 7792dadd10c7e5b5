"""Shared transformer building blocks.

Module/param names follow the conventions the sharding-rule presets match
(tpucfn/parallel/presets.py): q_proj/k_proj/v_proj/o_proj, gate_proj/
up_proj/down_proj, embed_tokens, lm_head. bf16 compute / fp32 params
throughout (MXU-native mixed precision).
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpucfn.ops.attention import dot_product_attention


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


def rope_frequencies(dim: int, max_pos: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """Precompute RoPE cos/sin tables: (max_pos, dim//2), fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: jax.Array) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,) global token positions."""
    c = cos[positions]  # (..., S, D/2)
    s = sin[positions]
    if c.ndim == 2:  # (S, D/2) -> broadcast batch
        c, s = c[None], s[None]
    c, s = c[:, :, None, :], s[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def causal_conv_silu(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
                     *, dtype: Any) -> jax.Array:
    """``silu(y)`` with ``y_t = sum_j w_j x_(t - width + 1 + j) (+ bias)`` a
    channel: the depthwise causal convolution in front of a recurrent mixer
    (Gated DeltaNet's q, k, v; Mamba-2's x, B, C).  x: (B, S, channels); w:
    (width, channels); float32 inside, ``dtype`` out."""
    width, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(width))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return nn.silu(y).astype(dtype)


# attention_fn(q, k, v, causal=..., q_offset=..., k_offset=...) -> out
AttentionFn = Callable[..., jax.Array]


class CausalSelfAttention(nn.Module):
    """GQA self-attention with RoPE; the attention inner op is pluggable so
    dense/flash/ring implementations swap without touching the module.

    ``decode=True`` turns on the autoregressive KV cache (flax ``cache``
    collection): each call appends this step's K/V at ``cache_index`` and
    attends over the whole prefix — the serving path. Cache capacity is
    ``max_seq``.
    """

    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 500000.0
    max_seq: int = 8192
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_fn: AttentionFn = dot_product_attention
    decode: bool = False

    @nn.compact
    def __call__(self, x, *, positions=None, q_offset=0):
        b, s, _ = x.shape
        dense = lambda feat, name: nn.DenseGeneral(  # noqa: E731
            feat, axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name,
        )
        q = dense(self.n_heads * self.head_dim, "q_proj")(x)
        k = dense(self.n_kv_heads * self.head_dim, "k_proj")(x)
        v = dense(self.n_kv_heads * self.head_dim, "v_proj")(x)
        q = q.reshape(b, s, self.n_heads, self.head_dim)
        k = k.reshape(b, s, self.n_kv_heads, self.head_dim)
        v = v.reshape(b, s, self.n_kv_heads, self.head_dim)

        cos, sin = rope_frequencies(self.head_dim, self.max_seq, self.rope_theta)

        if self.decode:
            # Positions come from the cache index; a caller-supplied
            # schedule (the ring/SP path) is incompatible with decode.
            # q_offset arrives as the model's traced zero and is ignored.
            if positions is not None:
                raise ValueError(
                    "decode mode derives positions from the KV cache index; "
                    "explicit positions are not supported together with decode"
                )
            cached_k = self.variable(
                "cache", "cached_key", jnp.zeros,
                (b, self.max_seq, self.n_kv_heads, self.head_dim), self.dtype,
            )
            cached_v = self.variable(
                "cache", "cached_value", jnp.zeros,
                (b, self.max_seq, self.n_kv_heads, self.head_dim), self.dtype,
            )
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            i = cache_index.value
            positions = i + jnp.arange(s)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            k_all = jax.lax.dynamic_update_slice(
                cached_k.value, k.astype(self.dtype), (0, i, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                cached_v.value, v.astype(self.dtype), (0, i, 0, 0))
            cached_k.value = k_all
            cached_v.value = v_all
            cache_index.value = i + s
            # q lives at global positions [i, i+s); cache slots beyond are
            # zeros and masked out by causality.
            out = self.attention_fn(q, k_all, v_all, causal=True,
                                    q_offset=i, k_offset=0)
            # Past-capacity decoding would silently clamp the RoPE gather
            # and the cache write; poison the output instead so overflow is
            # loud (NaNs) rather than quietly wrong.
            out = jnp.where(i + s <= self.max_seq, out, jnp.nan)
        else:
            if positions is None:
                positions = jnp.arange(s) + q_offset
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            out = self.attention_fn(q, k, v, causal=True,
                                    q_offset=q_offset, k_offset=q_offset)
        out = out.reshape(b, s, self.n_heads * self.head_dim)
        return dense(x.shape[-1], "o_proj")(out)


class SwiGLUMLP(nn.Module):
    ffn_dim: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda feat, name: nn.DenseGeneral(  # noqa: E731
            feat, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name,
        )
        gate = nn.silu(dense(self.ffn_dim, "gate_proj")(x))
        up = dense(self.ffn_dim, "up_proj")(x)
        return dense(x.shape[-1], "down_proj")(gate * up)
