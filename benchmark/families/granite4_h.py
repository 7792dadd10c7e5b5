"""Granite-4.0-H-Micro cut to one period of its layers, trained as
``examples/granite4_h.py`` trains it: ``models/ssm.SSMDecoder`` (Mamba-2 mixers
through the chunked state-space op, one attention layer without positional
embedding at the model's own scale, a tied and scaled head), Adafactor and
chunked cross-entropy as the other decoder cells'."""

from benchmark import flops_granite4_h
from benchmark.reference import granite4_h as reference  # noqa: F401


def build(config, mix, mesh, init_fn):
    import jax.numpy as jnp
    import optax

    from tpucfn.models.ssm import (SSMConfig, SSMDecoder, make_loss_fn,
                                   sharding_rules)
    from tpucfn.train import Trainer

    m, job = config["model"], config["job"]
    if m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise ValueError("the program derives the head size as hidden/heads; "
                         f"the configuration states {m['head_dim']}")
    if (m["mamba_n_heads"] * m["mamba_d_head"]
            != m["mamba_expand"] * m["hidden_size"]):
        raise ValueError("mamba_n_heads * mamba_d_head is not the expanded width")
    if m["position_embedding_type"] != "nope" or not m["mamba_conv_bias"]:
        raise ValueError("the program has no positional embedding and a "
                         "convolution with bias")
    cfg = SSMConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        layer_types=tuple(m["layer_types"]),
        ffn_dim=m["shared_intermediate_size"],
        embedding_multiplier=m["embedding_multiplier"],
        residual_multiplier=m["residual_multiplier"],
        logits_scaling=m["logits_scaling"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        attention_multiplier=m["attention_multiplier"],
        ssm_heads=m["mamba_n_heads"], ssm_head_dim=m["mamba_d_head"],
        ssm_state=m["mamba_d_state"], ssm_groups=m["mamba_n_groups"],
        conv_kernel=m["mamba_d_conv"], ssm_chunk=m["mamba_chunk_size"],
        norm_eps=m["rms_norm_eps"], remat=job["remat"],
        dtype=jnp.dtype(job["compute_dtype"]),
        param_dtype=jnp.dtype(job["param_dtype"]))
    if len(cfg.layer_types) != m["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    trainer = Trainer(mesh, sharding_rules(cfg),
                      make_loss_fn(SSMDecoder(cfg), ce_chunk=job["ce_chunk"]),
                      optax.adafactor(job["lr"]), init_fn)
    return trainer, mix["shape"]["batch"] * mix["shape"]["seq_len"]


def step_flops(model: dict, shape: dict) -> float:
    return 3 * flops_granite4_h.forward_flops(model, shape["batch"],
                                              shape["seq_len"])
