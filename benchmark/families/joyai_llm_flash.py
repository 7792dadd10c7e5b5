"""JoyAI-LLM-Flash cut to one chip's share of an eight-chip layer, trained as
``examples/joyai_llm_flash.py`` trains it: ``models/latent.LatentDecoder``
(latent attention in every block, one leading dense layer, scanned sparse
layers, the multi-token-prediction block), the routed expert layer with the
sigmoid router told which experts it holds, Adafactor and chunked
cross-entropy as the other decoder cells'."""

from benchmark import flops_joyai_llm_flash
from benchmark.reference import joyai_llm_flash as reference  # noqa: F401


def build(config, mix, mesh, init_fn):
    import jax.numpy as jnp
    import optax

    from tpucfn.models.latent import (LatentConfig, LatentDecoder, make_loss_fn,
                                      sharding_rules)
    from tpucfn.train import Trainer

    m, job = config["model"], config["job"]
    if m["qk_head_dim"] != m["qk_nope_head_dim"] + m["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not the two parts' sum")
    cfg = LatentConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], first_dense=m["first_k_dense_replace"],
        dense_ffn_dim=m["intermediate_size"], n_heads=m["num_attention_heads"],
        q_rank=m["q_lora_rank"], kv_rank=m["kv_lora_rank"],
        qk_nope_dim=m["qk_nope_head_dim"], qk_rope_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"], rope_theta=m["rope_theta"],
        n_experts=m["router_experts"], top_k=m["num_experts_per_tok"],
        expert_dim=m["moe_intermediate_size"],
        shared_expert_dim=m["n_shared_experts"] * m["moe_intermediate_size"],
        routed_scale=m["routed_scaling_factor"],
        held_experts=(0, m["n_routed_experts"]), mtp_lambda=m["mtp_lambda"],
        norm_eps=m["rms_norm_eps"], remat=job["remat"],
        dtype=jnp.dtype(job["compute_dtype"]),
        param_dtype=jnp.dtype(job["param_dtype"]))
    trainer = Trainer(mesh, sharding_rules(cfg),
                      make_loss_fn(LatentDecoder(cfg), ce_chunk=job["ce_chunk"]),
                      optax.adafactor(job["lr"]), init_fn)
    return trainer, mix["shape"]["batch"] * mix["shape"]["seq_len"]


def step_flops(model: dict, shape: dict) -> float:
    return 3 * flops_joyai_llm_flash.forward_flops(model, shape["batch"],
                                                   shape["seq_len"])
