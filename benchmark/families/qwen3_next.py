"""Qwen3-Next-80B-A3B cut to one chip's share of an eight-chip layer, trained
as ``examples/qwen3_next_moe.py`` trains it: ``models/hybrid.HybridDecoder``
(the period-scanned decoder), the routed expert layer told which experts it
holds, Adafactor and chunked cross-entropy as the other decoder cells'."""

from benchmark import flops_qwen3_next
from benchmark.reference import qwen3_next as reference  # noqa: F401


def build(config, mix, mesh, init_fn):
    import jax.numpy as jnp
    import optax

    from tpucfn.models.hybrid import (HybridConfig, HybridDecoder, make_loss_fn,
                                      sharding_rules)
    from tpucfn.train import Trainer

    m, job = config["model"], config["job"]
    cfg = HybridConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        full_attention_interval=m["full_attention_interval"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], partial_rotary_factor=m["partial_rotary_factor"],
        rope_theta=m["rope_theta"],
        linear_key_heads=m["linear_num_key_heads"],
        linear_value_heads=m["linear_num_value_heads"],
        linear_key_dim=m["linear_key_head_dim"],
        linear_value_dim=m["linear_value_head_dim"],
        conv_kernel=m["linear_conv_kernel_dim"],
        n_experts=m["router_experts"], top_k=m["num_experts_per_tok"],
        expert_dim=m["moe_intermediate_size"],
        shared_expert_dim=m["shared_expert_intermediate_size"],
        held_experts=(0, m["num_experts"]), norm_eps=m["rms_norm_eps"],
        remat=job["remat"], dtype=jnp.dtype(job["compute_dtype"]),
        param_dtype=jnp.dtype(job["param_dtype"]))
    trainer = Trainer(mesh, sharding_rules(cfg),
                      make_loss_fn(HybridDecoder(cfg), ce_chunk=job["ce_chunk"]),
                      optax.adafactor(job["lr"]), init_fn)
    return trainer, mix["shape"]["batch"] * mix["shape"]["seq_len"]


def step_flops(model: dict, shape: dict) -> float:
    return 3 * flops_qwen3_next.forward_flops(model, shape["batch"],
                                              shape["seq_len"])
