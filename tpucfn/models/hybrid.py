"""A decoder written as a repeated period of layer kinds.

``Llama`` scans one uniform block.  The hybrid decoders that followed it
interleave kinds of token mixer in a fixed ratio; this model states the
stack as a *period*, ``interval - 1`` Gated DeltaNet layers and then one
gated softmax-attention layer, each followed by the same sparse
feed-forward, and scans the period: parameters are stacked ``(periods,
interval - 1, ...)`` under ``periods/linear`` and ``(periods, ...)`` under
``periods/full``, so compile time does not grow with depth and every layer
is rematerialised on its own.

The layers, as their published modelling code computes them:

* norms are zero-centred RMS norms, ``x * rsqrt(mean(x^2) + eps) * (1 + w)``;
* **gated attention**: the query projection is twice as wide and splits per
  head into a query and a gate; query and key are normalised per head;
  rotary embedding turns the first ``partial_rotary_factor`` of a head's
  dims and passes the rest; causal softmax attention (through
  ``kernels/auto.py``, as ``Llama`` calls it); the output is multiplied by
  ``sigmoid(gate)`` before the output projection;
* **Gated DeltaNet**: projections to q, k, v and an output gate z, and to
  two scalars a head (b, a); a depthwise causal convolution and SiLU over
  q, k and v; q and k normalised to unit length; the gated delta rule
  (``ops/gated_delta.py``) with ``beta = sigmoid(b)`` and log-decay
  ``-exp(A_log) * softplus(a + dt_bias)``; a per-head RMS norm gated by
  ``SiLU(z)``; the output projection;
* **sparse feed-forward**: ``models/moe.RoutedExperts``, told which experts
  this chip holds, with the shared expert.

Training only: there is no cache for the recurrent state yet, so ``serve/``
and ``hf_convert`` refuse this model type by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpucfn.mesh import AXIS_FSDP
from tpucfn.models.layers import (AttentionFn, apply_rope, causal_conv_silu,
                                  rope_frequencies)
from tpucfn.models.llama import chunked_causal_lm_loss, remat_policy
from tpucfn.models.moe import KernelParam, RoutedExperts
from tpucfn.ops.gated_delta import gated_delta_rule
from tpucfn.parallel.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4     # the period: 3 linear, then 1 full
    # gated attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # Gated DeltaNet
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    delta_chunk: int = 64
    # sparse feed-forward: the router's width, and the experts held here
    n_experts: int = 512
    top_k: int = 10
    expert_dim: int = 512
    shared_expert_dim: int = 512
    held_experts: tuple[int, int] = (0, 512)
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

    @property
    def periods(self) -> int:
        return self.n_layers // self.full_attention_interval

    def layer_plan(self) -> "LayerPlan":
        return LayerPlan(HybridPeriod, self.periods, ZeroCentredRMSNorm)

    @classmethod
    def tiny(cls, vocab: int = 256) -> "HybridConfig":
        return cls(vocab_size=vocab, dim=64, n_layers=4, n_heads=4,
                   n_kv_heads=2, head_dim=16, linear_key_heads=2,
                   linear_value_heads=4, linear_key_dim=16,
                   linear_value_dim=16, delta_chunk=16, n_experts=8, top_k=2,
                   expert_dim=32, shared_expert_dim=32, held_experts=(0, 8),
                   dtype=jnp.float32)


def _unit_rms(x, eps):
    """``x`` over the root of its mean square along the last axis, float32."""
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)


class ZeroCentredRMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        return (_unit_rms(x, self.eps) * (1.0 + w)).astype(self.dtype)


class GatedRMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * w * SiLU(z)`` over the last axis; ``w``
    starts at 1 and is not zero-centred."""

    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, z):
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        return (_unit_rms(x, self.eps) * w
                * nn.silu(z.astype(jnp.float32))).astype(self.dtype)


def _dense(cfg: HybridConfig, features: int, name: str):
    return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name,
                           kernel_init=nn.initializers.normal(0.02))


class GatedAttention(nn.Module):
    cfg: HybridConfig
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        qg = _dense(cfg, h * 2 * hd, "q_proj")(x).reshape(b, s, h, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = _dense(cfg, hkv * hd, "k_proj")(x).reshape(b, s, hkv, hd)
        v = _dense(cfg, hkv * hd, "v_proj")(x).reshape(b, s, hkv, hd)
        q = ZeroCentredRMSNorm(cfg.norm_eps, cfg.dtype, name="q_norm")(q)
        k = ZeroCentredRMSNorm(cfg.norm_eps, cfg.dtype, name="k_norm")(k)
        rot = int(hd * cfg.partial_rotary_factor)
        cos, sin = rope_frequencies(rot, s, cfg.rope_theta)
        turn = lambda t: jnp.concatenate(  # noqa: E731
            [apply_rope(t[..., :rot], cos, sin, jnp.arange(s)), t[..., rot:]],
            axis=-1)
        out = self.attention_fn(turn(q), turn(k), v, causal=True)
        out = out * nn.sigmoid(gate)
        return _dense(cfg, x.shape[-1], "o_proj")(out.reshape(b, s, h * hd))


class GatedDeltaNet(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
        f32 = jnp.float32
        qkv = jnp.concatenate([_dense(cfg, hk * dk, "q_proj")(x),
                               _dense(cfg, hk * dk, "k_proj")(x),
                               _dense(cfg, hv * dv, "v_proj")(x)], axis=-1)
        z = _dense(cfg, hv * dv, "z_proj")(x).reshape(b, s, hv, dv)
        beta = nn.sigmoid(_dense(cfg, hv, "b_proj")(x).astype(f32))
        a = _dense(cfg, hv, "a_proj")(x).astype(f32)
        # as published: A uniform on (0, 16), dt_bias ones
        a_log = self.param("A_log", lambda key, shape: jnp.log(jax.random.uniform(
            key, shape, f32, 1e-3, 16.0)), (hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), f32)
        g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)

        width = cfg.conv_kernel
        w = KernelParam((width, qkv.shape[-1]), cfg.param_dtype,
                        nn.initializers.normal(width ** -0.5), name="conv")()
        qkv = causal_conv_silu(qkv, w, dtype=cfg.dtype)

        q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
        unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        q = (unit(q.reshape(b, s, hk, dk).astype(f32)) * dk ** -0.5).astype(cfg.dtype)
        k = unit(k.reshape(b, s, hk, dk).astype(f32)).astype(cfg.dtype)
        # rematerialised on its own inside the layer's remat: what the rule's
        # scan keeps (its inputs and a state a chunk) and the expert layer's
        # rows are then never held at once in the backward pass (0.95 GB of
        # a 16 GB chip at 2 x 8,192, with the preparation in its kernel)
        o = jax.checkpoint(
            lambda *a: gated_delta_rule(*a, chunk_size=cfg.delta_chunk)
        )(q, k, v.reshape(b, s, hv, dv), g, beta).astype(f32)

        o = GatedRMSNorm(cfg.norm_eps, cfg.dtype, name="norm")(o, z)
        return _dense(cfg, x.shape[-1], "out_proj")(o.reshape(b, s, hv * dv))


class HybridLayer(nn.Module):
    """``h = x + mixer(norm(x)); y = h + ffn(norm(h))``, in scan's
    ``(carry, _) -> (carry, out)`` shape; ``out`` is the feed-forward's
    routing counters."""

    cfg: HybridConfig
    kind: str                       # "linear" | "full"
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        norm = lambda name: ZeroCentredRMSNorm(  # noqa: E731
            cfg.norm_eps, cfg.dtype, name=name)
        mixer = (GatedAttention(cfg, self.attention_fn, name="mixer")
                 if self.kind == "full" else GatedDeltaNet(cfg, name="mixer"))
        x = x + mixer(norm("input_norm")(x))
        h, stats = RoutedExperts(
            cfg.n_experts, cfg.top_k, cfg.expert_dim, cfg.held_experts,
            shared_dim=cfg.shared_expert_dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="mlp")(norm("post_attn_norm")(x))
        return x + h, stats


class HybridPeriod(nn.Module):
    cfg: HybridConfig
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        layer = HybridLayer
        do_remat, policy = remat_policy(cfg.remat)
        if do_remat:
            layer = nn.remat(layer, prevent_cse=False, policy=policy)
        x, linear = nn.scan(
            layer, variable_axes={"params": 0}, split_rngs={"params": True},
            length=cfg.full_attention_interval - 1,
        )(cfg, "linear", self.attention_fn, name="linear")(x)
        x, full = layer(cfg, "full", self.attention_fn, name="full")(x)
        return x, jax.tree.map(lambda a, b: jnp.append(a, b), linear, full)


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """What a decoder is made of between its embedding and its head: layers
    of their own before the trunk, a scanned run of one period, and a block
    after the trunk.  Every entry is a module class called ``(cfg,
    attention_fn, name=...)``; a leading layer and the period take and return
    scan's ``(carry, _) -> (carry, counters)``."""

    period: Any                     # scanned ``periods`` times under ``trunk``
    periods: int
    norm: Any                       # ``(eps, dtype, name=...)``: the final norm
    trunk: str = "periods"
    leading: tuple[tuple[str, Any], ...] = ()   # (name, module), in order
    # ``(x, tokens, embed) -> (hidden, counters)`` from the trunk's output
    # before the final norm, the tokens and the embedding module itself
    after: tuple[str, Any] | None = None
    # the embedding's output is multiplied by the one, the logits divided by
    # the other; a tied head is the embedding's own table and no ``lm_head``
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_embeddings: bool = False


class PlannedDecoder(nn.Module):
    """Embedding, the layers of ``cfg.layer_plan()``, final norm, and a head
    of its own or the embedding's table: the one decoder ``HybridDecoder``,
    ``models/latent.LatentDecoder`` and ``models/ssm.SSMDecoder`` are."""

    cfg: Any
    # None = the automatic dense/flash dispatch of kernels/auto.py
    attention_fn: AttentionFn | None = None

    @nn.compact
    def __call__(self, tokens, *, return_hidden: bool = False):
        """tokens (B, S) -> (logits (B, S, vocab) float32, counters), or the
        final hidden states in place of the logits with ``return_hidden``
        (pair it with ``chunked_causal_lm_loss``).  ``counters`` maps each of
        the feed-forward's routing counters to one value a sparse layer, in
        order.  Where the plan has a block after the trunk, the hidden states
        are a pair (the trunk's, that block's) and so are the logits: the
        head is one module, called twice."""
        cfg = self.cfg
        plan: LayerPlan = cfg.layer_plan()
        attention_fn = self.attention_fn
        if attention_fn is None:
            from tpucfn.kernels.auto import auto_attention_static_zero

            attention_fn = auto_attention_static_zero
        embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed_tokens",
                         embedding_init=nn.initializers.normal(0.02))
        x = embed(tokens)
        if plan.embedding_multiplier != 1.0:
            x = x * plan.embedding_multiplier
        found = []
        for name, layer in plan.leading:
            x, c = layer(cfg, attention_fn, name=name)(x)
            found.append(c)
        x, c = nn.scan(
            plan.period, variable_axes={"params": 0},
            split_rngs={"params": True}, length=plan.periods,
        )(cfg, attention_fn, name=plan.trunk)(x)
        found.append(c)
        hidden = plan.norm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        if plan.after is not None:
            name, block = plan.after
            second, c = block(cfg, attention_fn, name=name)(x, tokens, embed)
            found.append(c)
            hidden = (hidden, second)
        counters = jax.tree.map(
            lambda *parts: jnp.concatenate([p.reshape(-1) for p in parts]),
            *[c for c in found if c])
        if return_hidden:
            return hidden, counters
        if plan.tie_embeddings:
            table = embed.embedding.astype(jnp.float32)
            head = lambda h: jnp.einsum(  # noqa: E731
                "...d,vd->...v", h, table) / plan.logits_scaling
        else:
            head = nn.DenseGeneral(
                cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                param_dtype=cfg.param_dtype, name="lm_head",
                kernel_init=nn.initializers.normal(0.02))
        return jax.tree.map(lambda h: head(h.astype(jnp.float32)), hidden), counters


class HybridDecoder(PlannedDecoder):
    """The period-scanned decoder of this module (``HybridConfig``)."""


def routing_counters(c: dict) -> dict:
    """A step's routing counters from a decoder's ``counters`` (one value a
    sparse layer each): assignments that fell on held experts (mean over the
    layers), the largest held expert's rows over the mean (worst layer),
    assignments lost (sum), and the blocks of rows a layer ran (mean)."""
    return {"moe_rows": jnp.mean(c["rows"]),
            "moe_load_max_over_mean": jnp.max(c["load_max_over_mean"]),
            "moe_dropped": jnp.sum(c["dropped"]),
            "moe_blocks_run": jnp.mean(c["blocks_run"])}


def make_loss_fn(model: HybridDecoder, *, ce_chunk: int = 512):
    """The ``Trainer`` loss: chunked next-token cross-entropy, and beside
    ``accuracy`` the step's ``routing_counters`` as ``counters``
    (``run_train_loop`` writes whatever a loss function returns under that
    name to the trace and to gauges)."""

    def loss_fn(params, mstate, batch, rng):
        hidden, c = model.apply({"params": params}, batch["tokens"],
                                return_hidden=True)
        loss, acc = chunked_causal_lm_loss(
            hidden, params["lm_head"]["kernel"], batch["tokens"],
            chunk_size=ce_chunk)
        return loss, ({"accuracy": acc, "counters": routing_counters(c)}, mstate)

    return loss_fn


def sharding_rules(cfg: HybridConfig) -> ShardingRules:
    """FSDP rules for the stacked tree: one chip needs none of them, and on
    a mesh each kernel is split over ``fsdp`` on its model dimension.  The
    leading stack axes (periods, and the layer inside a period under
    ``periods/linear``) are not sharded; the experts held are whole on every
    chip of the mesh (this layer makes no exchange)."""
    f = AXIS_FSDP
    rules = []
    for prefix, lead in ((r"periods/linear/", (None, None)),
                         (r"periods/full/", (None,))):
        def spec(*axes, lead=lead):
            full = lead + axes
            while full and full[-1] is None:
                full = full[:-1]
            return P(*full)

        rules += [
            (prefix + r".*experts/(gate_proj|up_proj)/kernel$", spec(None, f)),
            (prefix + r".*experts/down_proj/kernel$", spec(None, None, f)),
            (prefix + r".*(o_proj|out_proj|down_proj)/kernel$", spec(None, f)),
            (prefix + r".*_proj/kernel$", spec(f)),
            (prefix + r".*(router|shared_expert_gate)/kernel$", spec(f)),
        ]
    return ShardingRules(tuple(rules) + (
        (r"embed_tokens/embedding$", P(None, f)),
        (r"lm_head/kernel$", P(f)),
        (r".*", P()),
    ))


RECURRENT_MODEL_TYPES = ("qwen3_next", "granitemoehybrid")


def refuse_recurrent_model(what, where: str) -> None:
    """Serving and checkpoint conversion are out of scope for a decoder with
    recurrent layers (Gated DeltaNet here, Mamba-2 in ``models/ssm.py``), and
    say so by name: ``what`` is a model, a config of either module, or a
    published config (``model_type``)."""
    from tpucfn.models.ssm import SSMConfig, SSMDecoder

    if (isinstance(what, (HybridDecoder, HybridConfig, SSMDecoder, SSMConfig))
            or getattr(what, "model_type", None) in RECURRENT_MODEL_TYPES):
        raise NotImplementedError(
            f"{where} cannot take a decoder with recurrent layers "
            f"({type(what).__name__}): there is no cache for recurrent state "
            "yet (ROADMAP R4, D3); models/hybrid.py and models/ssm.py train "
            "only")
