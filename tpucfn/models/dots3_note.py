"""A decoder of DeepSeek sparse attention and windowed latent attention (the
published ``dots3_note`` configuration at small widths).

The layers, pre-norm (``h = x + attn(norm(x))``, ``y = h + ffn(norm(h))``,
RMS norms, no biases), with ``x̂`` the normed input of the attention:

* **full layers**: ``models/latent.LatentAttention`` whose attention runs
  over the keys an indexer chose.  The indexer (``ops/dsa.py``): ``qi =
  c_q W_q`` (``index_heads`` of ``index_dim``) and one key ``ki =
  LayerNorm(x̂ W_k)``, rotary embedding over adjacent pairs of the first
  ``index_rope_dim`` dims of both at the layer's theta, weights ``w = x̂ W_w
  / sqrt(index_heads)``; ``I[t, s] = Σ_j w_j ReLU(qi_j · ki_s) /
  sqrt(index_dim)``; each query attends to the ``index_topk`` causal keys
  of largest ``I`` (all of them while there are fewer).  Its inputs
  ``x̂`` and ``c_q`` are detached: the indexer learns from the KL loss
  alone, ``KL(p_t ‖ softmax(I[t, S_t]))`` with ``p_t`` the attention's
  probabilities over ``S_t`` averaged over the heads held, detached,
  averaged over positions and reported as the ``dsa_kl`` counter;
* **window layers**: latent attention of their own widths, ranks and theta
  over the last ``window`` positions (query ``t`` sees key ``s`` where
  ``0 <= t - s < window``);
* both kinds multiply each head's output by ``sigmoid(x̂ W_g)`` of its own
  (headwise gate) before the output projection;
* **feed-forward**: the leading ``first_dense`` layers a SwiGLU, every later
  one ``models/moe.RoutedExperts`` with the sigmoid router, its selection
  bias, told which experts this chip holds, and an ungated shared expert.

``layer_types`` names each layer's kind; after the leading dense layers
the kinds repeat a period that starts with a full layer, scanned by
``models/hybrid.PlannedDecoder`` (``layers/full``, ``layers/window_<i>``).
The heads held (``n_heads``, ``swa_heads``) may be a chip's share of the
published heads: every head's part of the output is its own.  Training
only: serving would need a cache of the latents, one that knows the
window and one of the indexer's keys, which no cache here holds yet
(ROADMAP R2, R12), so ``serve/`` and ``hf_convert`` refuse this model
type by name.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from tpucfn.kernels.flash_attention import FLASH_SELECT
from tpucfn.mesh import AXIS_FSDP
from tpucfn.models.hybrid import (LayerPlan, PlannedDecoder, merge_counters,
                                  routing_counters)
from tpucfn.models.latent import (LatentAttention, LatentConfig,
                                  rope_adjacent_pairs)
from tpucfn.models.layers import AttentionFn, RMSNorm, SwiGLUMLP
from tpucfn.models.llama import chunked_causal_lm_loss, remat_policy
from tpucfn.models.moe import RoutedExperts
from tpucfn.ops import dsa
from tpucfn.parallel.sharding import ShardingRules

MODEL_TYPE = "dots3_note"


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152064
    dim: int = 5120
    # each layer's kind, "full" or "window"; the first ``first_dense`` dense.
    # The published 46 end on a full layer past the last whole period; the
    # default is the leading layer and one period
    layer_types: tuple[str, ...] = ("full", "full", "window", "window",
                                    "window")
    first_dense: int = 1
    dense_ffn_dim: int = 13824
    # full layers' latent attention
    n_heads: int = 128
    q_rank: int = 1024
    kv_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    # window layers' latent attention
    window: int = 513
    swa_heads: int = 64
    swa_q_rank: int = 1024
    swa_kv_rank: int = 1024
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    # the indexer of the full layers
    index_heads: int = 64
    index_dim: int = 128
    index_rope_dim: int = 64
    index_topk: int = 2048
    index_kl_weight: float = 1.0
    # sparse feed-forward: the router's width, and the experts held here
    n_experts: int = 256
    top_k: int = 8
    expert_dim: int = 1536
    shared_expert_dim: int = 1536
    routed_scale: float = 1.0
    held_experts: tuple[int, int] = (0, 256)
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool | str = True             # per layer; see llama.remat_policy

    def __post_init__(self):
        remat_policy(self.remat)
        self.period()

    def period(self) -> int:
        """The length of the repeated run after the leading dense layers,
        which starts with a full layer; the leading layers are full."""
        lead, rest = (self.layer_types[:self.first_dense],
                      self.layer_types[self.first_dense:])
        n = rest[1:].index("full") + 1 if "full" in rest[1:] else len(rest)
        unit = ("full",) + ("window",) * (n - 1)
        if set(lead) != {"full"} or not rest or rest != unit * (len(rest) // n):
            raise ValueError(f"layer kinds {self.layer_types} are not full "
                             "leading layers and whole periods of a full "
                             "layer and window layers")
        return n

    def latent(self, kind: str) -> LatentConfig:
        """The widths of one kind of latent attention, as ``LatentConfig``."""
        common = dict(dim=self.dim, norm_eps=self.norm_eps, dtype=self.dtype,
                      param_dtype=self.param_dtype, remat=self.remat)
        if kind == "full":
            return LatentConfig(
                n_heads=self.n_heads, q_rank=self.q_rank, kv_rank=self.kv_rank,
                qk_nope_dim=self.qk_nope_dim, qk_rope_dim=self.qk_rope_dim,
                v_head_dim=self.v_head_dim, rope_theta=self.rope_theta, **common)
        return LatentConfig(
            n_heads=self.swa_heads, q_rank=self.swa_q_rank,
            kv_rank=self.swa_kv_rank, qk_nope_dim=self.swa_qk_nope_dim,
            qk_rope_dim=self.swa_qk_rope_dim, v_head_dim=self.swa_v_head_dim,
            rope_theta=self.swa_rope_theta, **common)

    def layer_plan(self) -> LayerPlan:
        n = self.period()
        return LayerPlan(
            period=Dots3Period,
            periods=(len(self.layer_types) - self.first_dense) // n,
            norm=RMSNorm, trunk="layers",
            leading=tuple((f"dense_{i}", _rematted(self, DenseLayer))
                          for i in range(self.first_dense)))

    @classmethod
    def tiny(cls, vocab: int = 256) -> "Dots3Config":
        return cls(vocab_size=vocab, dim=64,
                   layer_types=("full", "full", "window", "window", "window"),
                   dense_ffn_dim=128, n_heads=4, q_rank=48, kv_rank=32,
                   qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, window=9,
                   swa_heads=2, swa_q_rank=32, swa_kv_rank=40,
                   swa_qk_nope_dim=24, swa_qk_rope_dim=8, swa_v_head_dim=16,
                   index_heads=4, index_dim=16, index_rope_dim=8, index_topk=12,
                   n_experts=8, top_k=2, expert_dim=32, shared_expert_dim=32,
                   held_experts=(0, 8), dtype=jnp.float32)


def _rematted(cfg: Dots3Config, layer):
    """Every layer here is a module of its own, none inside a scan of its
    kind: without ``prevent_cse`` the compiler merges a layer's
    rematerialised forward with the first and keeps its activations."""
    do_remat, policy = remat_policy(cfg.remat)
    return nn.remat(layer, prevent_cse=True, policy=policy) if do_remat else layer


def _dense(cfg: Dots3Config, features: int, name: str, dtype=None):
    return nn.DenseGeneral(features, use_bias=False, dtype=dtype or cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name,
                           kernel_init=nn.initializers.normal(0.02))


class Indexer(nn.Module):
    """The indexer's queries (B, HI, S, DI), key (B, S, DI) and weights
    (B, S, HI) float32, from detached inputs."""

    cfg: Dots3Config

    @nn.compact
    def __call__(self, x, c_q):
        cfg = self.cfg
        b, s, _ = x.shape
        hi, di, rot = cfg.index_heads, cfg.index_dim, cfg.index_rope_dim
        x, c_q = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)
        turn = lambda t: jnp.concatenate(  # noqa: E731
            [rope_adjacent_pairs(t[..., :rot], cfg.rope_theta), t[..., rot:]],
            axis=-1)
        qi = turn(_dense(cfg, hi * di, "wq_b")(c_q).reshape(b, s, hi, di))
        ki = nn.LayerNorm(1e-6, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                          name="k_norm")(_dense(cfg, di, "wk")(x))
        ki = turn(ki.reshape(b, s, 1, di))[:, :, 0]
        w = _dense(cfg, hi, "weights_proj")(x).astype(jnp.float32) * (
            (hi * di) ** -0.5)
        return jnp.swapaxes(qi, 1, 2), ki, w


class Dots3Layer(nn.Module):
    """One block in scan's ``(carry, _) -> (carry, counters)`` shape: the
    feed-forward's routing counters (a sparse one) and, in a full layer,
    ``dsa_kl`` and ``dsa_select_share`` (chosen keys over causal keys)."""

    cfg: Dots3Config
    attention_fn: AttentionFn
    kind: str = "full"              # "full" | "window"
    sparse: bool = True

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)  # noqa: E731
        xn = norm("input_norm")(x)
        mixer = LatentAttention(
            cfg.latent(self.kind), self.attention_fn, gate=True,
            window=cfg.window if self.kind == "window" else None, name="mixer")
        if self.kind == "full":
            attend = functools.partial(_attend, cfg, self.attention_fn,
                                       Indexer(cfg, name="indexer"), xn)
            mixed, counters = mixer(xn, attend=attend)
        else:
            mixed, counters = mixer(xn), {}
        x = x + mixed
        h = norm("post_attn_norm")(x)
        if self.sparse:
            h, stats = RoutedExperts(
                cfg.n_experts, cfg.top_k, cfg.expert_dim, cfg.held_experts,
                shared_dim=cfg.shared_expert_dim, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, score="sigmoid", select_bias=True,
                weight_scale=cfg.routed_scale, shared_gate=False, name="mlp")(h)
            counters = {**counters, **stats}
        else:
            h = SwiGLUMLP(cfg.dense_ffn_dim, cfg.dtype, cfg.param_dtype,
                          name="mlp")(h)
        return x + h, counters


def _attend(cfg: Dots3Config, attention_fn, indexer, xn, q, k, v, c_q):
    """Attention over the indexer's choice, and the indexer's loss."""
    with jax.named_scope("dsa_index"):
        qi, ki, w = indexer(xn, c_q)
    with jax.named_scope("dsa_select"):
        words, lse_i = dsa.select(qi, ki, w, topk=cfg.index_topk)
        words, lse_i = checkpoint_name((words, lse_i), FLASH_SELECT)
    # what the check compares the reference on (``mutable=["intermediates"]``)
    indexer.sow("intermediates", "dsa_words", words)
    with jax.named_scope("dsa_attend"):
        out, lse = attention_fn(q, k, v, causal=True, select=words,
                                return_lse=True)
    with jax.named_scope("dsa_kl"):
        kl = dsa.kl(qi, ki, w, words, lse_i, q, k, lse,
                    scale=q.shape[-1] ** -0.5)
    b, s = q.shape[:2]
    share = (jnp.sum(jax.lax.population_count(words)).astype(jnp.float32)
             / (b * s * (s + 1) / 2))
    return out, {"dsa_kl": kl, "dsa_select_share": share}


class DenseLayer(Dots3Layer):
    sparse: bool = False


class Dots3Period(nn.Module):
    """A full layer, then the window layers of the period, each a module of
    its own (``window_<i>``): scanned, the compiler casts the three layers'
    stacked experts to bfloat16 at once and holds them beside their
    gradients (1.1 GB more at the cell's plan)."""

    cfg: Dots3Config
    attention_fn: AttentionFn

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        layer = _rematted(cfg, Dots3Layer)
        found = []
        for i, kind in enumerate(["full"] + ["window"] * (cfg.period() - 1)):
            x, c = layer(cfg, self.attention_fn, kind,
                         name="full" if i == 0 else f"window_{i - 1}")(x)
            found.append(c)
        return x, merge_counters(found)


class Dots3Decoder(PlannedDecoder):
    """``PlannedDecoder`` over a ``Dots3Config``."""


def make_loss_fn(model: Dots3Decoder, *, ce_chunk: int = 512):
    """The ``Trainer`` loss: chunked next-token cross-entropy plus
    ``index_kl_weight`` times each full layer's indexer loss.  Beside
    ``accuracy`` the step's ``counters``: ``lm_loss``, ``dsa_kl`` and
    ``dsa_select_share`` (means over the full layers) and
    ``hybrid.routing_counters`` over the sparse layers."""
    cfg = model.cfg

    def loss_fn(params, mstate, batch, rng):
        tokens = batch["tokens"]
        hidden, c = model.apply({"params": params}, tokens, return_hidden=True)
        lm, acc = chunked_causal_lm_loss(
            hidden, params["lm_head"]["kernel"], tokens, chunk_size=ce_chunk)
        counters = {**routing_counters(c), "lm_loss": lm,
                    "dsa_kl": jnp.mean(c["dsa_kl"]),
                    "dsa_select_share": jnp.mean(c["dsa_select_share"])}
        loss = lm + cfg.index_kl_weight * jnp.sum(c["dsa_kl"])
        return loss, ({"accuracy": acc, "counters": counters}, mstate)

    return loss_fn


def refuse(what, where: str) -> None:
    """Serving and checkpoint conversion say by name that they cannot take
    this model: ``what`` is a model, its config, or a published config
    (``model_type``)."""
    if (isinstance(what, (Dots3Decoder, Dots3Config))
            or getattr(what, "model_type", None) == MODEL_TYPE):
        raise NotImplementedError(
            f"{where} cannot take a decoder with an indexer's choice of keys "
            f"and windowed latent attention ({type(what).__name__}, "
            f"{MODEL_TYPE}): no cache holds latents by a window or the "
            "indexer's keys yet (ROADMAP R2, R12); models/dots3_note.py "
            "trains only")


def sharding_rules(cfg: Dots3Config) -> ShardingRules:
    """FSDP rules: one chip needs none of them, and on a mesh each kernel is
    split over ``fsdp`` on its model dimension.  Stacked layers' leading
    axes are not sharded; the experts held are whole on every chip of the
    mesh (this layer makes no exchange)."""
    f = AXIS_FSDP
    rules = []
    for prefix, lead in ((r"layers/(full|window_\d+)/", (None,)),
                         (r"dense_\d+/", ())):
        def spec(*axes, lead=lead):
            full = lead + axes
            while full and full[-1] is None:
                full = full[:-1]
            return P(*full)

        rules += [
            (prefix + r".*experts/(gate_proj|up_proj)/kernel$", spec(None, f)),
            (prefix + r".*experts/down_proj/kernel$", spec(None, None, f)),
            (prefix + r".*(o_proj|down_proj)/kernel$", spec(None, f)),
            (prefix + r".*(q_b_proj|kv_b_proj|wq_b)/kernel$", spec(None, f)),
            (prefix + r".*(_proj|router|wk)/kernel$", spec(f)),
        ]
    return ShardingRules(tuple(rules) + (
        (r"embed_tokens/embedding$", P(None, f)),
        (r"lm_head/kernel$", P(f)),
        (r".*", P()),
    ))
