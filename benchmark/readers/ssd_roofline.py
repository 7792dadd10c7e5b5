"""The state-space recurrence's share of its roofline: the least time the chip
could take for the passes a step needs of it (one forward and one backward
pass a Mamba layer, ``benchmark/flops_granite4_h.ssd_call``: the larger of the
operations over the peak rate and the bytes over the peak bandwidth; what
rematerialisation repeats is not needed and is not counted), times the steps
in the traced interval, over the device time ``readers/ssd_time_share.py``
finds."""

from benchmark import flops, flops_granite4_h
from benchmark.readers import ssd_time_share


def read(ctx):
    found = ssd_time_share.seconds(ctx)
    if found is None:
        return None
    m, shape = ctx.config["model"], ctx.mix["shape"]
    a_step = m["layer_types"].count("mamba") * sum(
        flops.roofline_seconds(*flops_granite4_h.ssd_call(
            kind, shape["batch"], shape["seq_len"], m), ctx.peak)[0]
        for kind in flops_granite4_h.SSD_PASSES)
    steps = sum(n for _, _, n in ctx.cut())
    return 100.0 * a_step * steps / found[0]
