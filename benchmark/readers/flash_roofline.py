"""The flash kernels' share of their roofline: the least time the chip could
take for each call's shape (``benchmark/flops.py``: the larger of its causal
operations over the peak rate and its bytes over the peak bandwidth), summed
over the calls in the traced interval, over the summed device time of those
events.  ``kinds`` tells a call's kind (forward, key/value backward, query
backward) from the head of its event's name, which today is its HLO text: the
kernels carry no name of their own yet."""

import re

from benchmark import flops
from benchmark.readers import trace


def read(ctx, kinds: dict):
    cuts = ctx.cut()
    if cuts is None:
        return None
    model, shape = ctx.config["model"], ctx.mix["shape"]
    least = {k: flops.roofline_seconds(*flops.flash_call(
        k, shape["batch"], shape["seq_len"], model["num_attention_heads"],
        model["num_key_value_heads"], model["head_dim"]), ctx.peak)[0]
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
