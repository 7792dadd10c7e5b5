"""dots3-note-prev cut to one chip's share of a 32-chip layer, trained as
``examples/dots3_note.py`` trains it: ``models/dots3_note.Dots3Decoder``
(a leading dense layer of DeepSeek sparse attention, then periods of one
sparse-attention layer and three windowed latent-attention layers, headwise
gates, the routed expert layer with the sigmoid router told which experts it
holds), Adafactor and chunked cross-entropy as the other decoder cells'.

``selection`` hands the check what the program chose: ``drivers/train_dsa``
gives the reference the program's keys (``reference/dots3_note.py``)."""

from benchmark import flops_dots3_note
from benchmark.reference import dots3_note as reference  # noqa: F401


def config_of(m: dict, job: dict):
    import jax.numpy as jnp

    from tpucfn.models.dots3_note import MODEL_TYPE, Dots3Config

    if m["model_type"] != MODEL_TYPE or m["topk_method"] != "noaux_tc":
        raise ValueError("the program is dots3_note's with the sigmoid router")
    if (m["attention_gate_type"], m["swa_attention_gate_type"]) != (
            "headwise", "headwise") or m["attention_bias"] or m["tie_word_embeddings"]:
        raise ValueError("the program gates each head, has no bias and an "
                         "untied head")
    if (m["num_key_value_heads"], m["swa_num_key_value_heads"]) != (
            m["num_attention_heads"], m["swa_num_attention_heads"]):
        raise ValueError("latent attention has a key head a query head")
    kind = {"full_attention": "full", "sliding_attention": "window"}
    return Dots3Config(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        layer_types=tuple(kind[t] for t in m["layer_types"]),
        first_dense=m["first_k_dense_replace"],
        dense_ffn_dim=m["intermediate_size"], n_heads=m["num_attention_heads"],
        q_rank=m["q_lora_rank"], kv_rank=m["kv_lora_rank"],
        qk_nope_dim=m["qk_nope_head_dim"], qk_rope_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"], rope_theta=m["rope_theta"],
        window=m["sliding_window_size"], swa_heads=m["swa_num_attention_heads"],
        swa_q_rank=m["swa_q_lora_rank"], swa_kv_rank=m["swa_kv_lora_rank"],
        swa_qk_nope_dim=m["swa_qk_nope_head_dim"],
        swa_qk_rope_dim=m["swa_qk_rope_head_dim"],
        swa_v_head_dim=m["swa_v_head_dim"], swa_rope_theta=m["swa_rope_theta"],
        index_heads=m["index_n_heads"], index_dim=m["index_head_dim"],
        index_rope_dim=m["index_rope_head_dim"], index_topk=m["index_topk"],
        index_kl_weight=m["index_kl_weight"], n_experts=m["router_experts"],
        top_k=m["num_experts_per_tok"], expert_dim=m["moe_intermediate_size"],
        shared_expert_dim=m["n_shared_experts"] * m["moe_intermediate_size"],
        routed_scale=m["routed_scaling_factor"],
        held_experts=(0, m["n_routed_experts"]), norm_eps=m["rms_norm_eps"],
        remat=job["remat"], dtype=jnp.dtype(job["compute_dtype"]),
        param_dtype=jnp.dtype(job["param_dtype"]))


def build(config, mix, mesh, init_fn):
    import optax

    from tpucfn.models.dots3_note import (Dots3Decoder, make_loss_fn,
                                          sharding_rules)
    from tpucfn.train import Trainer

    cfg = config_of(config["model"], config["job"])
    trainer = Trainer(mesh, sharding_rules(cfg),
                      make_loss_fn(Dots3Decoder(cfg), ce_chunk=config["job"]["ce_chunk"]),
                      optax.adafactor(config["job"]["lr"]), init_fn)
    return trainer, mix["shape"]["batch"] * mix["shape"]["seq_len"]


def selection(config):
    """``(params, tokens) -> words`` (full layers, B, G, S, LANES): the keys
    the program's full layers choose, in order, at those weights."""
    import jax
    import jax.numpy as jnp

    from tpucfn.models.dots3_note import Dots3Decoder

    cfg = config_of(config["model"], config["job"])
    model = Dots3Decoder(cfg)

    @jax.jit
    def run(params, tokens):
        _, found = model.apply({"params": params}, tokens, return_hidden=True,
                               mutable=["intermediates"])
        found = found["intermediates"]
        lead = [found[f"dense_{i}"]["indexer"]["dsa_words"][0]
                for i in range(cfg.first_dense)]
        scanned = found["layers"]["full"]["indexer"]["dsa_words"][0]
        return jnp.concatenate([jnp.stack(lead), scanned])

    return run


def step_flops(model: dict, shape: dict) -> float:
    return flops_dots3_note.step_flops(model, shape["batch"], shape["seq_len"])
