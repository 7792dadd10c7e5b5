"""A decoder whose token mixers are mostly state-space layers (Mamba-2), with a
softmax-attention layer among them at the places ``layer_types`` names (the
published ``granitemoehybrid`` configuration without its routed experts).

The layers, as the published modelling code computes them (RMS norms with a
scale that starts at 1; no bias but the convolution's):

* the embedding's output is multiplied by ``embedding_multiplier``; every
  residual branch by ``residual_multiplier``: ``x = x + r * mixer(norm(x))``,
  then ``x = x + r * mlp(norm(x))``, the feed-forward a SwiGLU; the head is the
  embedding's table and the logits are divided by ``logits_scaling``;
* **attention**: q, k, v, o projections, grouped-query heads of ``dim //
  n_heads``, **no positional embedding**, causal softmax of
  ``attention_multiplier * q k^T`` (not the head size's root: the flash
  kernels and the dense path take the scale) through ``kernels/auto.py``;
* **Mamba-2**: ``[z | xBC | dt] = in_proj(h)``; a depthwise causal
  convolution with bias and SiLU over ``xBC``, which splits into ``x`` (heads
  of ``ssm_head_dim``), ``B`` and ``C`` (``ssm_groups`` groups of
  ``ssm_state``, a group shared by its heads); ``dt = softplus(dt + dt_bias)``,
  ``a = -exp(A_log)``; the recurrence ``ops/ssd.py`` in chunks of
  ``ssm_chunk`` (its chunk-local parts the Pallas kernels of
  ``kernels/ssd.py`` where the op's own rule says so, ``jnp`` elsewhere; the
  layer's rematerialisation is the only one: what the op keeps for its
  backward pass is its inputs and the chunks' states); ``y = norm(y *
  silu(z))``, the gate *before* one RMS norm over all the heads' channels;
  ``out_proj``.

The stack is ``models/hybrid.PlannedDecoder``'s: ``layer_types`` is a whole
number of repetitions of its shortest period, and a period is laid out as
*runs* of one kind (five Mamba layers, the attention layer, four Mamba layers
in the published 9 : 1), a run of several layers scanned, each layer
rematerialised on its own: parameters under ``periods/run<i>_<kind>`` with
leading axes ``(periods, run length)``, or ``(periods,)`` for a run of one.
Training only: serving would cache each Mamba layer's state and the last
``conv_kernel - 1`` inputs of its convolution, which no cache here holds yet
(ROADMAP R4), so ``serve/`` and ``hf_convert`` refuse this model type by name.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpucfn.mesh import AXIS_FSDP
from tpucfn.models.hybrid import LayerPlan, PlannedDecoder
from tpucfn.models.layers import (AttentionFn, RMSNorm, SwiGLUMLP,
                                  causal_conv_silu)
from tpucfn.models.llama import chunked_causal_lm_loss, remat_policy
from tpucfn.models.moe import KernelParam
from tpucfn.ops.ssd import ssd
from tpucfn.parallel.sharding import ShardingRules

# the published 9 : 1: the attention layer sixth of every ten
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    vocab_size: int = 100352
    dim: int = 2048
    layer_types: tuple[str, ...] = PERIOD * 4
    ffn_dim: int = 8192
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    # attention (no positional embedding)
    n_heads: int = 32
    n_kv_heads: int = 8
    attention_multiplier: float = 0.015625
    # Mamba-2
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    conv_kernel: int = 4
    ssm_chunk: int = 256
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool | str = True             # per layer; see llama.remat_policy

    def __post_init__(self):
        remat_policy(self.remat)
        kinds = set(self.layer_types)
        if "mamba" not in kinds or kinds - {"mamba", "attention"}:
            raise ValueError("layer_types holds 'mamba' layers and, among "
                             f"them, 'attention' layers; got {sorted(kinds)}")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads}/{self.n_kv_heads} heads over "
                             f"{self.dim}")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def period(self) -> tuple[str, ...]:
        """The shortest prefix whose repetition ``layer_types`` is."""
        n = len(self.layer_types)
        return next(self.layer_types[:p] for p in range(1, n + 1)
                    if n % p == 0
                    and self.layer_types[:p] * (n // p) == self.layer_types)

    @property
    def runs(self) -> tuple[tuple[str, int], ...]:
        """A period as (kind, how many in a row)."""
        return tuple((kind, len(list(group)))
                     for kind, group in itertools.groupby(self.period))

    def layer_plan(self) -> LayerPlan:
        return LayerPlan(SSMPeriod, len(self.layer_types) // len(self.period),
                         RMSNorm,
                         embedding_multiplier=self.embedding_multiplier,
                         logits_scaling=self.logits_scaling,
                         tie_embeddings=True)

    @classmethod
    def tiny(cls, vocab: int = 256) -> "SSMConfig":
        return cls(vocab_size=vocab, dim=64, layer_types=PERIOD, ffn_dim=128,
                   n_heads=4, n_kv_heads=2, attention_multiplier=0.0625,
                   ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_chunk=8,
                   dtype=jnp.float32)


def _dense(cfg: SSMConfig, features: int, name: str):
    return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name,
                           kernel_init=nn.initializers.normal(0.02))


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class Attention(nn.Module):
    cfg: SSMConfig
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = _dense(cfg, h * hd, "q_proj")(x).reshape(b, s, h, hd)
        k = _dense(cfg, hkv * hd, "k_proj")(x).reshape(b, s, hkv, hd)
        v = _dense(cfg, hkv * hd, "v_proj")(x).reshape(b, s, hkv, hd)
        out = self.attention_fn(q, k, v, causal=True,
                                scale=cfg.attention_multiplier)
        return _dense(cfg, x.shape[-1], "o_proj")(out.reshape(b, s, h * hd)), {}


class Mamba2Mixer(nn.Module):
    cfg: SSMConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        b, s, _ = h.shape
        nh, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
        inner, f32 = nh * p, jnp.float32
        conv_dim = inner + 2 * g * n
        z, xbc, dt = jnp.split(
            _dense(cfg, inner + conv_dim + nh, "in_proj")(h),
            [inner, inner + conv_dim], axis=-1)
        width = cfg.conv_kernel
        w = KernelParam((width, conv_dim), cfg.param_dtype,
                        nn.initializers.normal(width ** -0.5), name="conv")()
        bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), f32)
        xbc = causal_conv_silu(xbc, w, bias, dtype=cfg.dtype)
        x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        # as published: A uniform on (1, 16), dt log-uniform on (1e-3, 1e-1)
        # through the inverse of its softplus, D ones
        a_log = self.param("A_log", lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, f32, 1.0, 16.0)), (nh,))
        dt_bias = self.param("dt_bias", lambda key, shape: _inverse_softplus(
            jnp.exp(jax.random.uniform(key, shape, f32, jnp.log(1e-3),
                                       jnp.log(1e-1)))), (nh,))
        d = self.param("D", nn.initializers.ones, (nh,), f32)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        y, state, decay_min = ssd(
            x.reshape(b, s, nh, p), dt, -jnp.exp(a_log),
            bm.reshape(b, s, g, n), cm.reshape(b, s, g, n), d,
            chunk_size=cfg.ssm_chunk)
        y = y.reshape(b, s, inner).astype(f32) * nn.silu(z.astype(f32))
        y = RMSNorm(cfg.norm_eps, cfg.dtype, name="norm")(y)
        stats = jax.lax.stop_gradient({
            "log_decay_min": decay_min,
            "state_rms": jnp.sqrt(jnp.mean(jnp.square(state)))})
        return _dense(cfg, h.shape[-1], "out_proj")(y), stats


class SSMLayer(nn.Module):
    """``h = x + r * mixer(norm(x)); y = h + r * mlp(norm(h))``, in scan's
    ``(carry, _) -> (carry, out)`` shape; ``out`` is a Mamba mixer's two
    readings of its recurrence (nothing for an attention layer)."""

    cfg: SSMConfig
    kind: str                       # "mamba" | "attention"
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)  # noqa: E731
        r = cfg.residual_multiplier
        mixer = (Attention(cfg, self.attention_fn, name="mixer")
                 if self.kind == "attention" else Mamba2Mixer(cfg, name="mixer"))
        mixed, stats = mixer(norm("input_norm")(x))
        x = x + r * mixed
        h = SwiGLUMLP(cfg.ffn_dim, cfg.dtype, cfg.param_dtype, name="mlp")(
            norm("post_attn_norm")(x))
        return x + r * h, stats


class SSMPeriod(nn.Module):
    """One period of ``cfg.layer_types`` as its runs, in order."""

    cfg: SSMConfig
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        layer = SSMLayer
        do_remat, policy = remat_policy(cfg.remat)
        if do_remat:
            layer = nn.remat(layer, prevent_cse=False, policy=policy)
        found = []
        for i, (kind, length) in enumerate(cfg.runs):
            name = f"run{i}_{kind}"
            if length == 1:
                x, stats = layer(cfg, kind, self.attention_fn, name=name)(x)
            else:
                x, stats = nn.scan(
                    layer, variable_axes={"params": 0},
                    split_rngs={"params": True}, length=length,
                )(cfg, kind, self.attention_fn, name=name)(x)
            if stats:
                found.append(stats)
        return x, jax.tree.map(
            lambda *parts: jnp.concatenate([p.reshape(-1) for p in parts]),
            *found)


class SSMDecoder(PlannedDecoder):
    """``PlannedDecoder`` over an ``SSMConfig``: tied head, the embedding and
    the logits scaled."""


def recurrence_counters(c: dict) -> dict:
    """A step's readings of the recurrence from a decoder's ``counters`` (one
    value a Mamba layer each): the most negative log-decay summed inside one
    chunk, and the root mean square of the state a sequence ends with, both of
    the worst layer."""
    return {"ssm_log_decay_min": jnp.min(c["log_decay_min"]),
            "ssm_state_rms": jnp.max(c["state_rms"])}


def make_loss_fn(model: SSMDecoder, *, ce_chunk: int = 512):
    """The ``Trainer`` loss: chunked next-token cross-entropy over the
    embedding's own table, the hidden states divided by ``logits_scaling``
    first (which divides the logits), and beside ``accuracy`` the step's
    ``recurrence_counters`` as ``counters``."""
    cfg = model.cfg

    def loss_fn(params, mstate, batch, rng):
        hidden, c = model.apply({"params": params}, batch["tokens"],
                                return_hidden=True)
        loss, acc = chunked_causal_lm_loss(
            hidden / cfg.logits_scaling,
            params["embed_tokens"]["embedding"].T, batch["tokens"],
            chunk_size=ce_chunk)
        return loss, ({"accuracy": acc, "counters": recurrence_counters(c)},
                      mstate)

    return loss_fn


def sharding_rules(cfg: SSMConfig) -> ShardingRules:
    """FSDP rules: one chip needs none of them, and on a mesh each kernel is
    split over ``fsdp`` on its model dimension.  The leading stack axes (the
    periods, and the layer inside a scanned run) are not sharded."""
    f = AXIS_FSDP
    rules = []
    for i, (kind, length) in enumerate(cfg.runs):
        prefix = rf"periods/run{i}_{kind}/"
        lead = (None,) * (1 if length == 1 else 2)
        rules += [
            (prefix + r".*(o_proj|out_proj|down_proj)/kernel$", P(*lead, None, f)),
            (prefix + r".*_proj/kernel$", P(*lead, f)),
        ]
    return ShardingRules(tuple(rules) + (
        (r"embed_tokens/embedding$", P(None, f)),
        (r".*", P()),
    ))
