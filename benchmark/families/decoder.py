"""A Llama-shaped decoder (RoPE, RMSNorm, SwiGLU, GQA, untied head) trained as
``examples/llama3_8b_fsdp.py:156-343`` trains it, with the one-chip job that
``bench.py:420-457`` settled on (Adafactor, chunked cross-entropy): AdamW's
state does not fit one chip."""

from benchmark import flops
from benchmark.reference import decoder as reference  # noqa: F401


def build(config, mix, mesh, init_fn):
    import jax.numpy as jnp
    import optax

    from tpucfn.models.llama import (Llama, LlamaConfig,
                                     chunked_causal_lm_loss, sharding_rules)
    from tpucfn.train import Trainer

    model, job = config["model"], config["job"]
    cfg = LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        max_seq=model["max_position_embeddings"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        remat=job["remat"], dtype=jnp.dtype(job["compute_dtype"]),
        param_dtype=jnp.dtype(job["param_dtype"]))
    if cfg.head_dim != model["head_dim"]:
        raise ValueError("the program derives head_dim as hidden/heads; the "
                         f"configuration states {model['head_dim']}")
    net = Llama(cfg)

    def loss_fn(params, mstate, batch, rng):
        hidden = net.apply({"params": params}, batch["tokens"],
                           return_hidden=True)
        loss, acc = chunked_causal_lm_loss(
            hidden, params["lm_head"]["kernel"], batch["tokens"],
            chunk_size=job["ce_chunk"])
        return loss, ({"accuracy": acc}, mstate)

    trainer = Trainer(mesh, sharding_rules(cfg, tensor=False), loss_fn,
                      optax.adafactor(job["lr"]), init_fn)
    return trainer, mix["shape"]["batch"] * mix["shape"]["seq_len"]


def step_flops(model: dict, shape: dict) -> float:
    return 3 * flops.decoder_forward_flops(model, shape["batch"],
                                           shape["seq_len"])
