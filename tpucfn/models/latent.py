"""A decoder with multi-head latent attention (DeepSeek-V3's layers; the
published ``joyai_llm_flash`` configuration at small widths).

The layers, as the published modelling code computes them (RMS norms with a
scale that starts at 1, no biases):

* **latent attention**: queries and keys/values are projected down, normed
  and projected up again: ``c_q = norm(x W_qa)``, ``[q_nope | q_rot] = c_q
  W_qb`` a head; ``[c_kv | k_rot] = x W_kva``, ``[k_nope | v] = norm(c_kv)
  W_kvb`` a head.  Rotary embedding turns ``q_rot`` and the one ``k_rot``
  all heads share, over adjacent pairs (``rope_interleave``).  A head's query
  and key are ``qk_nope + qk_rope`` wide (192) and its value ``v_head_dim``
  (128): causal softmax attention through ``kernels/auto.py``, whose flash
  kernel takes values narrower than keys;
* **feed-forward**: the first ``first_dense`` layers a SwiGLU; every later
  one ``models/moe.RoutedExperts`` with the sigmoid router, its selection
  bias and weight scale, told which experts this chip holds, and an ungated
  shared expert;
* **multi-token prediction**: after the trunk, ``eh_proj`` over the normed
  trunk output beside the normed embedding of the next token, one sparse
  block and a norm of its own; the decoder's one embedding and one head serve
  both predictions (``make_loss_fn``: targets two ahead for the second).

The stack is ``models/hybrid.PlannedDecoder``'s: leading dense layers by
name (``dense_0``), the sparse layers scanned under ``layers``, the prediction
block after the trunk (``mtp``).  Training only: serving would cache the
latents ``c_kv`` and ``k_rot``, which no cache here holds yet (ROADMAP R3).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpucfn.mesh import AXIS_FSDP
from tpucfn.models.hybrid import LayerPlan, PlannedDecoder, routing_counters
from tpucfn.models.layers import AttentionFn, RMSNorm, SwiGLUMLP
from tpucfn.models.llama import chunked_causal_lm_loss, remat_policy
from tpucfn.models.moe import RoutedExperts
from tpucfn.parallel.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    vocab_size: int = 129280
    dim: int = 2048
    n_layers: int = 40                   # the trunk: dense first, then sparse
    first_dense: int = 1
    dense_ffn_dim: int = 7168
    # latent attention
    n_heads: int = 32
    q_rank: int = 1536
    kv_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    # sparse feed-forward: the router's width, and the experts held here
    n_experts: int = 256
    top_k: int = 8
    expert_dim: int = 768
    shared_expert_dim: int = 768
    routed_scale: float = 2.5
    held_experts: tuple[int, int] = (0, 256)
    mtp_lambda: float = 0.1              # weight of the second prediction's loss
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool | str = True             # per layer; see llama.remat_policy

    def __post_init__(self):
        remat_policy(self.remat)
        if not 0 <= self.first_dense < self.n_layers:
            raise ValueError(f"{self.first_dense} dense layers of {self.n_layers}")

    def layer_plan(self) -> LayerPlan:
        return LayerPlan(
            period=_rematted(self, SparseLayer),
            periods=self.n_layers - self.first_dense, norm=RMSNorm,
            trunk="layers",
            leading=tuple((f"dense_{i}", _rematted(self, DenseLayer))
                          for i in range(self.first_dense)),
            after=("mtp", NextTokenBlock))

    @classmethod
    def tiny(cls, vocab: int = 256) -> "LatentConfig":
        return cls(vocab_size=vocab, dim=64, n_layers=3, dense_ffn_dim=128,
                   n_heads=4, q_rank=48, kv_rank=32, qk_nope_dim=16,
                   qk_rope_dim=8, v_head_dim=16, n_experts=8, top_k=2,
                   expert_dim=32, shared_expert_dim=32, held_experts=(0, 8),
                   dtype=jnp.float32)


def _rematted(cfg: LatentConfig, layer):
    do_remat, policy = remat_policy(cfg.remat)
    return nn.remat(layer, prevent_cse=False, policy=policy) if do_remat else layer


def _dense(cfg: LatentConfig, features: int, name: str):
    return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name,
                           kernel_init=nn.initializers.normal(0.02))


def _norm(cfg: LatentConfig, name: str):
    return RMSNorm(cfg.norm_eps, cfg.dtype, name=name)


def rope_adjacent_pairs(x, theta: float):
    """Rotary embedding over adjacent pairs ``(2i, 2i + 1)`` of the last axis;
    x: (B, S, H, D), positions 0..S-1, float32 inside."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    cfg: LatentConfig
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, nope, rot = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
        c_q = _norm(cfg, "q_a_norm")(_dense(cfg, cfg.q_rank, "q_a_proj")(x))
        q = _dense(cfg, h * (nope + rot), "q_b_proj")(c_q).reshape(
            b, s, h, nope + rot)
        kva = _dense(cfg, cfg.kv_rank + rot, "kv_a_proj")(x)
        c_kv = _norm(cfg, "kv_a_norm")(kva[..., :cfg.kv_rank])
        k_rot = rope_adjacent_pairs(
            kva[..., cfg.kv_rank:].reshape(b, s, 1, rot), cfg.rope_theta)
        kv = _dense(cfg, h * (nope + cfg.v_head_dim), "kv_b_proj")(c_kv).reshape(
            b, s, h, nope + cfg.v_head_dim)
        q = jnp.concatenate(
            [q[..., :nope], rope_adjacent_pairs(q[..., nope:], cfg.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rot, (b, s, h, rot))], axis=-1)
        out = self.attention_fn(q, k, kv[..., nope:], causal=True)
        return _dense(cfg, x.shape[-1], "o_proj")(
            out.reshape(b, s, h * cfg.v_head_dim))


class _Layer(nn.Module):
    """``h = x + attention(norm(x)); y = h + ffn(norm(h))``, in scan's
    ``(carry, _) -> (carry, out)`` shape; ``out`` is the feed-forward's
    routing counters (none for a dense one)."""

    cfg: LatentConfig
    attention_fn: AttentionFn

    def ffn(self, x):
        raise NotImplementedError

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        x = x + LatentAttention(cfg, self.attention_fn, name="mixer")(
            _norm(cfg, "input_norm")(x))
        h, stats = self.ffn(_norm(cfg, "post_attn_norm")(x))
        return x + h, stats


class DenseLayer(_Layer):
    def ffn(self, x):
        cfg = self.cfg
        return SwiGLUMLP(cfg.dense_ffn_dim, cfg.dtype, cfg.param_dtype,
                         name="mlp")(x), {}


class SparseLayer(_Layer):
    def ffn(self, x):
        cfg = self.cfg
        return RoutedExperts(
            cfg.n_experts, cfg.top_k, cfg.expert_dim, cfg.held_experts,
            shared_dim=cfg.shared_expert_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, score="sigmoid", select_bias=True,
            weight_scale=cfg.routed_scale, shared_gate=False, name="mlp")(x)


class NextTokenBlock(nn.Module):
    """The multi-token-prediction block: from the trunk's output before the
    final norm, a hidden state that predicts the token after next.  It runs
    at all S positions, so that attention sees the step's one shape; the last
    position is fed the embedding of token 0 in place of the token past the
    end, and causality keeps it out of every earlier position."""

    cfg: LatentConfig
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x, tokens, embed):
        cfg = self.cfg
        ahead = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        both = jnp.concatenate(
            [_norm(cfg, "hnorm")(x), _norm(cfg, "enorm")(embed(ahead))], axis=-1)
        y, stats = _rematted(cfg, SparseLayer)(
            cfg, self.attention_fn, name="block")(
                _dense(cfg, cfg.dim, "eh_proj")(both))
        return _norm(cfg, "final_norm")(y), stats


class LatentDecoder(PlannedDecoder):
    """``PlannedDecoder`` over a ``LatentConfig``: with ``return_hidden`` the
    hidden states are the pair (trunk's, prediction block's)."""


def make_loss_fn(model: LatentDecoder, *, ce_chunk: int = 512):
    """The ``Trainer`` loss: chunked cross-entropy of the next token plus
    ``mtp_lambda`` times that of the token after next, both through the one
    ``lm_head`` kernel (and, inside the model, the one embedding).  Beside
    ``accuracy`` (the next token's) the step's ``counters``: the two losses,
    and ``hybrid.routing_counters`` over all sparse blocks (the trunk's and
    the prediction block's)."""
    cfg = model.cfg

    def loss_fn(params, mstate, batch, rng):
        tokens = batch["tokens"]
        (hidden, second), c = model.apply({"params": params}, tokens,
                                          return_hidden=True)
        head = params["lm_head"]["kernel"]
        lm, acc = chunked_causal_lm_loss(hidden, head, tokens,
                                         chunk_size=ce_chunk)
        mtp, _ = chunked_causal_lm_loss(second, head, tokens,
                                        chunk_size=ce_chunk, ahead=2)
        counters = {**routing_counters(c), "lm_loss": lm, "mtp_loss": mtp}
        return lm + cfg.mtp_lambda * mtp, (
            {"accuracy": acc, "counters": counters}, mstate)

    return loss_fn


def sharding_rules(cfg: LatentConfig) -> ShardingRules:
    """FSDP rules: one chip needs none of them, and on a mesh each kernel is
    split over ``fsdp`` on its model dimension.  The scanned layers' leading
    axis is not sharded; the experts held are whole on every chip of the mesh
    (this layer makes no exchange)."""
    f = AXIS_FSDP
    rules = []
    for prefix, lead in ((r"layers/", (None,)), (r"(dense_\d+|mtp/block)/", ())):
        def spec(*axes, lead=lead):
            full = lead + axes
            while full and full[-1] is None:
                full = full[:-1]
            return P(*full)

        rules += [
            (prefix + r".*experts/(gate_proj|up_proj)/kernel$", spec(None, f)),
            (prefix + r".*experts/down_proj/kernel$", spec(None, None, f)),
            (prefix + r".*(o_proj|down_proj)/kernel$", spec(None, f)),
            (prefix + r".*(q_b_proj|kv_b_proj)/kernel$", spec(None, f)),
            (prefix + r".*(_proj|router)/kernel$", spec(f)),
        ]
    return ShardingRules(tuple(rules) + (
        (r"mtp/eh_proj/kernel$", P(None, f)),
        (r"embed_tokens/embedding$", P(None, f)),
        (r"lm_head/kernel$", P(f)),
        (r".*", P()),
    ))
