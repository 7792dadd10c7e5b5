"""A part of dots3-note-prev's attention beside its roofline: the least time
a step's calls of the part need (``benchmark/flops_dots3_note.py``:
``index_step``, ``attend_step`` or ``swa_step``, over the pairs the rule
keeps and never a dense walk: the larger of the operations over the peak rate
and the bytes over the peak bandwidth), times the steps in the traced
interval, over the device time of the part's kernels (events whose names
match ``pattern``)."""

import re

from benchmark import flops, flops_dots3_note
from benchmark.readers import trace

PARTS = {"index": flops_dots3_note.index_step,
         "attend": flops_dots3_note.attend_step,
         "swa": flops_dots3_note.swa_step}


def read(ctx, part: str, pattern: str):
    cuts = ctx.cut()
    model, shape = ctx.config["model"], ctx.mix["shape"]
    if cuts is None or "index_topk" not in model:
        return None
    least, _ = flops.roofline_seconds(
        *PARTS[part](model, shape["batch"], shape["seq_len"]), ctx.peak)
    rx, spent = re.compile(pattern), 0.0
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        spent += sum(dur for name, _, dur in trace.clip(d.ops, t0, t1)
                     if rx.search(name))
    if spent == 0.0:
        return None
    return 100.0 * least * sum(n for _, _, n in cuts) / spent
