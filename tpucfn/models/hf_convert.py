"""HuggingFace Llama / Mixtral checkpoint import.

The adoption path for users arriving with standard weights: map a HF
``LlamaForCausalLM`` (or ``MixtralForCausalLM``) state dict onto the
tpucfn param tree (same rotate-half RoPE convention, so the mapping is
transpose/stack only — no head permutation) and derive
:class:`LlamaConfig` from the HF config. The parity tests pin our
models' logits against the canonical HF torch implementations on tiny
random models — a cross-implementation correctness check of
attention/RoPE/RMSNorm/SwiGLU (and, for Mixtral, the MoE routing/
expert math), not just plumbing.

Mixtral routing equivalence: HF's sparse MoE block softmaxes ALL
router logits, takes top-k, and renormalizes the kept probabilities —
literally the same order as tpucfn's ``_route``. The only semantic
difference is that HF is dropless while tpucfn is capacity-based, so
the import pins ``capacity_factor = E / top_k`` (capacity = every
token, exactly dropless for ANY routing; lower it after import if you
accept drops for memory).

Torch is only needed at conversion time (CPU is fine); nothing else in
tpucfn imports it.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from tpucfn.models.llama import LlamaConfig


def config_from_hf(hf_config: Any, **overrides) -> LlamaConfig:
    """LlamaConfig from a transformers ``LlamaConfig``-like object.

    Raises on HF features tpucfn's Llama does not implement rather than
    converting to silently-wrong numerics."""
    import dataclasses

    from tpucfn.models.hybrid import refuse_recurrent_model

    refuse_recurrent_model(hf_config, "hf_convert")
    scaling = getattr(hf_config, "rope_scaling", None)
    if scaling not in (None, {}):
        raise NotImplementedError(
            f"rope_scaling={scaling!r} is not implemented in tpucfn's RoPE "
            "(plain theta frequencies); converting would produce silently "
            "wrong positions (Llama-3.1+ checkpoints use this)")
    explicit_hd = getattr(hf_config, "head_dim", None)
    derived_hd = hf_config.hidden_size // hf_config.num_attention_heads
    if explicit_hd not in (None, derived_hd):
        raise NotImplementedError(
            f"head_dim={explicit_hd} != hidden_size//num_heads={derived_hd}: "
            "tpucfn's LlamaConfig derives head_dim, so this checkpoint's "
            "projection shapes cannot be represented")
    cfg = LlamaConfig(
        vocab_size=hf_config.vocab_size,
        dim=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads",
                           hf_config.num_attention_heads),
        ffn_dim=hf_config.intermediate_size,
        max_seq=hf_config.max_position_embeddings,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        norm_eps=float(hf_config.rms_norm_eps),
    )
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def _np(x) -> np.ndarray:
    """Torch tensor → numpy, PRESERVING dtype: a forced fp32 copy would
    quadruple host RAM on a real bf16 checkpoint (Mixtral-8x7B's expert
    stack alone is ~90 GB in fp32). bf16 has no native numpy dtype, so
    it round-trips through a uint16 view into ``ml_dtypes.bfloat16``
    (the dtype jax arrays use anyway)."""
    if hasattr(x, "detach"):
        t = x.detach().cpu()
        import torch

        if t.dtype == torch.bfloat16:
            import ml_dtypes

            return t.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
        x = t.numpy()
    return np.asarray(x)


def _convert_hf_state_dict(state_dict: Mapping[str, Any],
                           cfg: LlamaConfig, mlp_fn) -> dict:
    """Shared HF→tpucfn mapping core: embed, tied-or-separate lm_head,
    attention projections, norms, and the leftover-tensor refusal are
    identical across architectures; ``mlp_fn(take, lstack)`` supplies
    the per-architecture MLP sub-dict (dense SwiGLU for Llama, router +
    stacked experts for Mixtral). Torch Linear stores (out, in); flax
    DenseGeneral kernels are (in, out) — ``lstack`` transposes."""
    if not cfg.scan_layers:
        raise NotImplementedError(
            "HF import targets the scanned layout (cfg.scan_layers=True) — "
            "the unrolled layout is a test-only configuration")
    sd = state_dict
    L = cfg.n_layers
    consumed: set[str] = set()

    def take(name):
        consumed.add(name)
        return _np(sd[name])

    def lstack(fmt, transpose=True):
        mats = [take(fmt.format(i=i)) for i in range(L)]
        if transpose:
            mats = [m.T for m in mats]
        return np.stack(mats)

    embed = take("model.embed_tokens.weight")
    if "lm_head.weight" in sd:
        lm_head = take("lm_head.weight").T
    else:
        lm_head = embed.T.copy()

    layers = {
        "attn": {p: {"kernel": lstack(
            "model.layers.{i}.self_attn.%s.weight" % p)}
            for p in ("q_proj", "k_proj", "v_proj", "o_proj")},
        "mlp": mlp_fn(take, lstack),
        "input_norm": {"scale": lstack(
            "model.layers.{i}.input_layernorm.weight", transpose=False)},
        "post_attn_norm": {"scale": lstack(
            "model.layers.{i}.post_attention_layernorm.weight",
            transpose=False)},
    }
    params = {
        "embed_tokens": {"embedding": embed},
        "layers": layers,
        "final_norm": {"scale": take("model.norm.weight")},
        "lm_head": {"kernel": lm_head},
    }
    # A dropped tensor is silently-wrong logits (e.g. attention biases
    # from attention_bias=True checkpoints) — refuse instead.
    ignorable = {k for k in sd
                 if k.endswith("rotary_emb.inv_freq")}  # legacy buffer
    leftover = sorted(set(sd) - consumed - ignorable)
    if leftover:
        raise NotImplementedError(
            f"unmapped tensors in the HF state dict (first 5: "
            f"{leftover[:5]}) — this checkpoint uses features tpucfn "
            "does not implement (e.g. attention biases)")
    return params


def params_from_hf_state_dict(state_dict: Mapping[str, Any],
                              cfg: LlamaConfig) -> dict:
    """HF Llama ``model.state_dict()`` → the tpucfn param tree
    (scan-stacked when ``cfg.scan_layers``)."""
    def mlp(take, lstack):
        return {p: {"kernel": lstack("model.layers.{i}.mlp.%s.weight" % p)}
                for p in ("gate_proj", "up_proj", "down_proj")}

    return _convert_hf_state_dict(state_dict, cfg, mlp)


def from_hf_llama(hf_model: Any, **config_overrides
                  ) -> tuple[LlamaConfig, dict]:
    """(cfg, params) from a live ``transformers.LlamaForCausalLM``."""
    cfg = config_from_hf(hf_model.config, **config_overrides)
    return cfg, params_from_hf_state_dict(hf_model.state_dict(), cfg)


def config_from_hf_mixtral(hf_config: Any, **overrides) -> LlamaConfig:
    """LlamaConfig (with ``moe``) from a transformers ``MixtralConfig``.

    Capacity is pinned exactly dropless (see module docstring): the
    layer computes ``capacity = round(cf * T * k / E)``, so cf = E/k
    yields exactly T (round, not truncate — float dust must not shave
    one slot off when k does not divide E). Aux-loss coefficients are
    tpucfn defaults (they do not affect the forward)."""
    import dataclasses

    from tpucfn.models.moe import MoEConfig

    sliding = getattr(hf_config, "sliding_window", None)
    if sliding is not None:
        raise NotImplementedError(
            f"sliding_window={sliding} attention is not implemented "
            "(tpucfn attends full-causal); converting would silently "
            "change the attention pattern")
    base = config_from_hf(
        # MixtralConfig carries the same attention/embedding fields.
        hf_config)
    e = hf_config.num_local_experts
    k = hf_config.num_experts_per_tok
    cfg = dataclasses.replace(
        base, moe=MoEConfig(n_experts=e, top_k=k,
                            capacity_factor=float(e) / k))
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def params_from_hf_mixtral_state_dict(state_dict: Mapping[str, Any],
                                      cfg: LlamaConfig) -> dict:
    """HF Mixtral ``state_dict()`` → the tpucfn param tree. Attention,
    norms, embed and head map exactly as Llama (shared core); per-expert
    torch Linears w1/w3/w2 (gate/up/down, (out, in)) stack into the
    (E, D, F)/(E, F, D) expert kernels, and the router ``gate`` maps to
    ``router/kernel`` (D, E)."""
    if cfg.moe is None:
        raise ValueError("params_from_hf_mixtral_state_dict needs a MoE "
                         "config (use config_from_hf_mixtral)")
    E = cfg.moe.n_experts

    def mlp(take, lstack):
        def estack(w):  # (L, E, in, out) from per-layer per-expert Linears
            return np.stack([np.stack([take(
                f"model.layers.{i}.block_sparse_moe.experts.{e}.{w}.weight"
            ).T for e in range(E)]) for i in range(cfg.n_layers)])

        return {
            "router": {"kernel": lstack(
                "model.layers.{i}.block_sparse_moe.gate.weight")},
            # Mixtral MLP is w2(silu(w1 x) * w3 x) == our
            # wd(silu(x wg) * (x wu)).
            "experts/gate_proj/kernel": estack("w1"),
            "experts/up_proj/kernel": estack("w3"),
            "experts/down_proj/kernel": estack("w2"),
        }

    return _convert_hf_state_dict(state_dict, cfg, mlp)


def from_hf_mixtral(hf_model: Any, **config_overrides
                    ) -> tuple[LlamaConfig, dict]:
    """(cfg, params) from a live ``transformers.MixtralForCausalLM``."""
    cfg = config_from_hf_mixtral(hf_model.config, **config_overrides)
    return cfg, params_from_hf_mixtral_state_dict(hf_model.state_dict(), cfg)
