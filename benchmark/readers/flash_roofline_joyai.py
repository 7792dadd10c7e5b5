"""``flash_roofline`` for a model whose keys and values differ in head size
(latent attention): the head sizes are the model's ``qk_head_dim`` and
``v_head_dim``, every query head has a key head of its own, and the least
time of a call is ``benchmark/flops_joyai_llm_flash.flash_call``'s, which
counts those sizes and never a padded one.  Otherwise as
``readers/flash_roofline.py``: summed over the calls in the traced interval,
over the summed device time of those events, kinds told by ``kinds``."""

import re

from benchmark import flops, flops_joyai_llm_flash
from benchmark.readers import trace


def read(ctx, kinds: dict):
    cuts = ctx.cut()
    model, shape = ctx.config["model"], ctx.mix["shape"]
    if cuts is None or "qk_head_dim" not in model:
        return None
    heads = model["num_attention_heads"]
    least = {k: flops.roofline_seconds(*flops_joyai_llm_flash.flash_call(
        k, shape["batch"], shape["seq_len"], heads, heads,
        model["qk_head_dim"], model["v_head_dim"]), ctx.peak)[0]
        for k in kinds}
    rx = {k: re.compile(p) for k, p in kinds.items()}
    ideal = spent = 0.0
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        for name, _, dur in trace.clip(d.ops, t0, t1):
            kind = next((k for k, r in rx.items() if r.search(name)), None)
            if kind is not None:
                ideal += least[kind]
                spent += dur
    if spent == 0.0:
        return None
    return 100.0 * ideal / spent
