"""Device time of the gated delta rule, preparation and scan, over device
busy time.

The rule's operations are the compiler's own and carry no name of the
program's, so they are told by what they hold: a *chunk tensor* is an array
with a batch, a key-head, a chunk-count and a chunk-length axis (``B``, ``Hk``,
``ceil(S / chunk)`` and ``chunk`` among its dimensions, in any order: the decay
mask, ``K K^T``, the triangular inverse and its products, the chunked q, k, v
and what the scan is fed), and the scan is the ``while`` whose first carried
array is the float32 state ``(B, Hk, Hv / Hk, Dk, Dv)``.  An operation belongs
to the rule if the head of its text (``trace.NAME_CHARS``: its result and first
operands) shows a chunk tensor, or if it is such a scan, whose event spans its
body's.  Loops and branches otherwise belong to nothing: they span whole
layers.  The sizes are the configuration's and the mix's; ``chunk`` is the
program's (``HybridConfig.delta_chunk``) and comes with the metric's file.
"""

import re

from benchmark.readers import trace

ARRAY = re.compile(r"\b(?:pred|[a-z]+\d+)\[([0-9,]+)\]")
CONTROL = re.compile(r"^%(while|cond|call)\b")


def holds(name: str, dims: tuple[int, ...]) -> bool:
    """Whether some array in ``name`` has all of ``dims`` among its axes."""
    for m in ARRAY.finditer(name):
        axes = m.group(1).split(",")
        try:
            for d in dims:
                axes.remove(str(d))
        except ValueError:
            continue
        return True
    return False


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
    rule, busy = 0.0, 0.0
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        ops = trace.clip(d.ops, t0, t1)
        rule += trace.busy_seconds([
            e for e in ops if scan.search(e[0])
            or (not CONTROL.search(e[0]) and holds(e[0], chunk_tensor))])
        busy += trace.busy_seconds(ops)
    if rule == 0.0:
        return None
    return 100.0 * rule / busy
