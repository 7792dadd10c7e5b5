"""Olmo-Hybrid-7B cut to one period of its layers and a quarter of its
vocabulary, trained as ``examples/olmo_hybrid.py`` trains it:
``models/olmo_hybrid.OlmoHybridDecoder`` (three Gated DeltaNet layers with
negative eigenvalues, then full attention without positions, in Olmo's
reordered block), Adafactor and chunked cross-entropy as the other decoder
cells'."""

from benchmark import flops_olmo_hybrid
from benchmark.reference import olmo_hybrid as reference  # noqa: F401


def build(config, mix, mesh, init_fn):
    import jax.numpy as jnp
    import optax

    from tpucfn.models.olmo_hybrid import (OlmoHybridConfig, OlmoHybridDecoder,
                                           make_loss_fn, sharding_rules)
    from tpucfn.train import Trainer

    m, job = config["model"], config["job"]
    periods, linear = reference.layout(m)
    if m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise ValueError("the program derives the head size as hidden/heads; "
                         f"the configuration states {m['head_dim']}")
    if (m["rope_parameters"]["rope_theta"] is not None or m["attention_bias"]
            or m["tie_word_embeddings"] or m["hidden_act"] != "silu"):
        raise ValueError("the program has no positional embedding, no bias, "
                         "an untied head and SiLU")
    cfg = OlmoHybridConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], full_attention_interval=linear + 1,
        ffn_dim=m["intermediate_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        linear_key_heads=m["linear_num_key_heads"],
        linear_value_heads=m["linear_num_value_heads"],
        linear_key_dim=m["linear_key_head_dim"],
        linear_value_dim=m["linear_value_head_dim"],
        conv_kernel=m["linear_conv_kernel_dim"],
        allow_neg_eigval=m["linear_allow_neg_eigval"],
        norm_eps=m["rms_norm_eps"], remat=job["remat"],
        dtype=jnp.dtype(job["compute_dtype"]),
        param_dtype=jnp.dtype(job["param_dtype"]))
    trainer = Trainer(mesh, sharding_rules(cfg),
                      make_loss_fn(OlmoHybridDecoder(cfg), ce_chunk=job["ce_chunk"]),
                      optax.adafactor(job["lr"]), init_fn)
    return trainer, mix["shape"]["batch"] * mix["shape"]["seq_len"]


def step_flops(model: dict, shape: dict) -> float:
    return 3 * flops_olmo_hybrid.forward_flops(model, shape["batch"],
                                               shape["seq_len"])
