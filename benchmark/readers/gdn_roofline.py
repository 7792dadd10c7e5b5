"""The gated delta rule's share of its roofline: the least time the chip could
take for the passes a step needs of it (one forward and one backward pass a
DeltaNet layer, ``benchmark/flops_olmo_hybrid.gdn_call``: the larger of the
operations over the peak rate and the bytes over the peak bandwidth; what
rematerialisation repeats is not needed and is not counted), times the steps
in the traced interval, over the device time of the operations
``readers/gdn_time_share.py`` counts as the rule's (the preparation, its
kernels' chunk tensors, and the scan over chunks)."""

import re

from benchmark import flops, flops_olmo_hybrid
from benchmark.readers import gdn_time_share, trace


def read(ctx, chunk: int):
    cuts = ctx.cut()
    if cuts is None:
        return None
    m, shape = ctx.config["model"], ctx.mix["shape"]
    b, hk = shape["batch"], m["linear_num_key_heads"]
    chunk_tensor = (b, hk, -(-shape["seq_len"] // chunk), chunk)
    state = "f32[%d,%d,%d,%d,%d]" % (
        b, hk, m["linear_num_value_heads"] // hk, m["linear_key_head_dim"],
        m["linear_value_head_dim"])
    scan = re.compile(r"^%while[.\w]* = \(s32\[\][^,]*, " + re.escape(state))
    spent = 0.0
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        spent += trace.busy_seconds([
            e for e in trace.clip(d.ops, t0, t1) if scan.search(e[0])
            or (not gdn_time_share.CONTROL.search(e[0])
                and gdn_time_share.holds(e[0], chunk_tensor))])
    if spent == 0.0:
        return None
    a_step = m["layer_types"].count("linear_attention") * sum(
        flops.roofline_seconds(*flops_olmo_hybrid.gdn_call(
            kind, b, shape["seq_len"], m, chunk), ctx.peak)[0]
        for kind in flops_olmo_hybrid.GDN_PASSES)
    steps = sum(n for _, _, n in cuts)
    return 100.0 * a_step * steps / spent
