"""The expert layer's grouped matrix products' share of their roofline: the
least time the chip could take for one such product over the rows the step
reported (``benchmark/flops_qwen3_next.grouped_product``: the larger of its
operations over the peak rate and its bytes over the peak bandwidth), times
the products in the traced interval, over their summed device time.  Forward,
the rows' gradient and the weights' gradient multiply the same rows by the same
widths, so one least time serves every event ``pattern`` matches.  The rows are
the mean of the span attribute the loop writes a step (``rows_attr`` of
``rows_span``): assignments that fell on held experts, a layer."""

import re

from benchmark import flops, flops_qwen3_next
from benchmark.readers import span_attr_mean, trace


def read(ctx, pattern: str, rows_span: str, rows_attr: str):
    cuts = ctx.cut()
    rows = span_attr_mean.read(ctx, rows_span, rows_attr)
    if cuts is None or rows is None:
        return None
    least, _ = flops.roofline_seconds(
        *flops_qwen3_next.grouped_product(rows, ctx.config["model"]), ctx.peak)
    rx, calls, spent = re.compile(pattern), 0, 0.0
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        for name, _, dur in trace.clip(d.ops, t0, t1):
            if rx.search(name):
                calls += 1
                spent += dur
    if spent == 0.0:
        return None
    return 100.0 * calls * least / spent
