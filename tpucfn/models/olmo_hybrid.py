"""A decoder of Gated DeltaNet layers and full softmax attention in Olmo's
reordered block (the published ``olmo_hybrid`` configuration).

The layers, as the published configuration describes them (RMS norms with a
scale that starts at 1, not zero-centred; no bias anywhere):

* **the block** norms what a branch returns, not what it takes:
  ``h = x + norm(mixer(x)); y = h + norm(mlp(h))``, the feed-forward a dense
  SwiGLU, the same for both kinds of layer; a final norm, then an untied head;
* **Gated DeltaNet** (``models/hybrid.GatedDeltaNet``, the layer
  ``qwen3_next`` runs): a key head to each value head, keys of
  ``linear_key_dim`` and values of ``linear_value_dim``; ``beta = 2 *
  sigmoid(b)`` where ``allow_neg_eigval`` holds, so that the transition
  ``I - beta k k^T`` has an eigenvalue in (-1, 1) along ``k``;
* **attention**: multi-head q, k, v, o projections; Olmo's QK-norm, one RMS
  norm over the whole query projection and one over the whole key projection,
  before the heads are split; **no positional embedding** (the published
  ``rope_theta`` is null); causal softmax at the head size's root through
  ``kernels/auto.py``.

The stack is ``models/hybrid.PlannedDecoder``'s: ``full_attention_interval - 1``
DeltaNet layers, then one attention layer, as a scanned period, every layer
rematerialised on its own; parameters under ``periods/linear`` with leading
axes ``(periods, interval - 1)`` and ``periods/full`` with ``(periods,)``.
Training only: serving would cache each DeltaNet layer's state and the last
``conv_kernel - 1`` inputs of its convolution, which no cache here holds yet
(ROADMAP R4), so ``serve/`` and ``hf_convert`` refuse this model type by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

# the stacked tree is laid out as models/hybrid's, so its FSDP rules fit it
from tpucfn.models.hybrid import (GatedDeltaNet, LayerPlan, PlannedDecoder,
                                  sharding_rules)
from tpucfn.models.layers import AttentionFn, RMSNorm, SwiGLUMLP
from tpucfn.models.llama import chunked_causal_lm_loss, remat_policy

__all__ = ["OlmoHybridConfig", "OlmoHybridDecoder", "make_loss_fn",
           "sharding_rules"]


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    dim: int = 3840
    n_layers: int = 32
    full_attention_interval: int = 4     # the period: 3 linear, then 1 full
    ffn_dim: int = 11008
    # attention (no positional embedding)
    n_heads: int = 30
    n_kv_heads: int = 30
    # Gated DeltaNet
    linear_key_heads: int = 30
    linear_value_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    delta_chunk: int = 64
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool | str = True             # per layer; see llama.remat_policy

    def __post_init__(self):
        remat_policy(self.remat)
        if self.n_layers % self.full_attention_interval:
            raise ValueError(
                f"{self.n_layers} layers are no whole number of periods of "
                f"{self.full_attention_interval}")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads}/{self.n_kv_heads} heads over "
                             f"{self.dim}")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def layer_plan(self) -> LayerPlan:
        return LayerPlan(OlmoHybridPeriod,
                         self.n_layers // self.full_attention_interval, RMSNorm)

    @classmethod
    def tiny(cls, vocab: int = 256) -> "OlmoHybridConfig":
        """The block at test size: keys of 12 and values of 24 (no multiple
        of the lanes either), 4 heads of each kind."""
        return cls(vocab_size=vocab, dim=64, n_layers=4, ffn_dim=128,
                   n_heads=4, n_kv_heads=4, linear_key_heads=4,
                   linear_value_heads=4, linear_key_dim=12,
                   linear_value_dim=24, delta_chunk=8, dtype=jnp.float32)


def _dense(cfg: OlmoHybridConfig, features: int, name: str):
    return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name,
                           kernel_init=nn.initializers.normal(0.02))


class Attention(nn.Module):
    cfg: OlmoHybridConfig
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)  # noqa: E731
        q = norm("q_norm")(_dense(cfg, h * hd, "q_proj")(x)).reshape(b, s, h, hd)
        k = norm("k_norm")(_dense(cfg, hkv * hd, "k_proj")(x)).reshape(b, s, hkv, hd)
        v = _dense(cfg, hkv * hd, "v_proj")(x).reshape(b, s, hkv, hd)
        out = self.attention_fn(q, k, v, causal=True)
        return _dense(cfg, x.shape[-1], "o_proj")(out.reshape(b, s, h * hd)), {}


class OlmoHybridLayer(nn.Module):
    """``h = x + norm(mixer(x)); y = h + norm(mlp(h))``, in scan's ``(carry,
    _) -> (carry, out)`` shape; ``out`` is a DeltaNet layer's readings of its
    rule (nothing for an attention layer)."""

    cfg: OlmoHybridConfig
    kind: str                       # "linear" | "full"
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)  # noqa: E731
        if self.kind == "full":
            mixer = Attention(cfg, self.attention_fn, name="mixer")
        else:
            mixer = GatedDeltaNet.of(
                cfg, beta_scale=2.0 if cfg.allow_neg_eigval else 1.0,
                name="mixer")
        mixed, readings = mixer(x)
        h = x + norm("post_mixer_norm")(mixed)
        y = SwiGLUMLP(cfg.ffn_dim, cfg.dtype, cfg.param_dtype, name="mlp")(h)
        return h + norm("post_mlp_norm")(y), readings


class OlmoHybridPeriod(nn.Module):
    cfg: OlmoHybridConfig
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        layer = OlmoHybridLayer
        do_remat, policy = remat_policy(cfg.remat)
        if do_remat:
            layer = nn.remat(layer, prevent_cse=False, policy=policy)
        x, readings = nn.scan(
            layer, variable_axes={"params": 0}, split_rngs={"params": True},
            length=cfg.full_attention_interval - 1,
        )(cfg, "linear", self.attention_fn, name="linear")(x)
        x, _ = layer(cfg, "full", self.attention_fn, name="full")(x)
        return x, readings


class OlmoHybridDecoder(PlannedDecoder):
    """``PlannedDecoder`` over an ``OlmoHybridConfig``: untied head, final
    RMS norm."""


def rule_counters(c: dict) -> dict:
    """A step's readings of the delta rule from a decoder's ``counters`` (one
    value a DeltaNet layer each): the share of (position, value head) pairs
    with ``beta`` above 1, where the negative eigenvalue acts (mean over the
    layers), and the root mean square of the state a sequence ends with
    (worst layer)."""
    return {"gdn_reflect_share": jnp.mean(c["reflect_share"]),
            "gdn_state_rms": jnp.max(c["state_rms"])}


def make_loss_fn(model: OlmoHybridDecoder, *, ce_chunk: int = 512):
    """The ``Trainer`` loss: chunked next-token cross-entropy over the head,
    and beside ``accuracy`` the step's ``rule_counters`` as ``counters``."""

    def loss_fn(params, mstate, batch, rng):
        hidden, c = model.apply({"params": params}, batch["tokens"],
                                return_hidden=True)
        loss, acc = chunked_causal_lm_loss(
            hidden, params["lm_head"]["kernel"], batch["tokens"],
            chunk_size=ce_chunk)
        return loss, ({"accuracy": acc, "counters": rule_counters(c)}, mstate)

    return loss_fn

