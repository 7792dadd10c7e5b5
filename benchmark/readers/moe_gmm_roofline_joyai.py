"""``moe_gmm_roofline`` for a configuration whose key for the experts held is
``n_routed_experts``: the least time of one grouped product over the rows the
step reported is ``benchmark/flops_joyai_llm_flash.grouped_product``'s;
otherwise as ``readers/moe_gmm_roofline.py`` (the same ``moe_rows`` attribute
of ``step_metrics``, the same events)."""

import re

from benchmark import flops, flops_joyai_llm_flash
from benchmark.readers import span_attr_mean, trace


def read(ctx, pattern: str, rows_span: str, rows_attr: str):
    cuts = ctx.cut()
    rows = span_attr_mean.read(ctx, rows_span, rows_attr)
    if cuts is None or rows is None or "n_routed_experts" not in ctx.config["model"]:
        return None
    least, _ = flops.roofline_seconds(*flops_joyai_llm_flash.grouped_product(
        rows, ctx.config["model"]), ctx.peak)
    rx, calls, spent = re.compile(pattern), 0, 0.0
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        for name, _, dur in trace.clip(d.ops, t0, t1):
            if rx.search(name):
                calls += 1
                spent += dur
    if spent == 0.0:
        return None
    return 100.0 * calls * least / spent
