"""Device time of the state-space recurrence over device busy time.

Whatever implements the recurrence, its operations are told by what they hold,
as ``readers/gdn_time_share.py`` tells the delta rule's (the compiler's
operations carry no name of the program's):

- a *chunk tensor*: an array with the chunk count ``ceil(S / chunk)`` and the
  chunk length among its axes, and the batch where it is more than 1 (the
  chunked x, B, C and dt, the summed log-decays, ``C_i . B_j``, the decays of
  every head, the re-tiling copies between the layer's layout and these);
- a *state tensor*: an array with the chunk count and a head's state (head
  count, head size, state size) among its axes (what every chunk adds to the
  state and the state every chunk starts from);
- the scan over chunks: the ``while`` whose first carried array is the float32
  state ``(B, groups, heads / groups, head, state)``, whose event spans its
  body's.

An operation belongs to the recurrence if the head of its text
(``trace.NAME_CHARS``: its result and first operands) shows one of these.  Loops
and branches otherwise belong to nothing: they span whole layers.  Sizes are
the configuration's (``mamba_chunk_size`` among them) and the mix's; a
configuration without them has nothing to read.  A kernel of the program's own
for any part of the recurrence is added to ``KERNELS`` by the name it gives its
``pallas_call``.
"""

import re

from benchmark.readers import trace
from benchmark.readers.gdn_time_share import CONTROL, holds

KERNELS = re.compile(r"^%ssd_\w+[.0-9]* = ")


def seconds(ctx):
    """(the recurrence's device seconds, busy seconds) summed over the
    devices' cuts; None where there is nothing to read."""
    cuts = ctx.cut()
    m, shape = ctx.config["model"], ctx.mix["shape"]
    if cuts is None or "mamba_chunk_size" not in m:
        return None
    chunk, b = m["mamba_chunk_size"], shape["batch"]
    n = -(-shape["seq_len"] // chunk)
    heads, g = m["mamba_n_heads"], m["mamba_n_groups"]
    lead = (b,) if b > 1 else ()
    chunk_tensor = lead + (n, chunk)
    state_tensor = lead + (n, heads // g, m["mamba_d_head"], m["mamba_d_state"])
    state = "f32[%d,%d,%d,%d,%d]" % (b, g, heads // g, m["mamba_d_head"],
                                     m["mamba_d_state"])
    scan = re.compile(r"^%while[.\w]* = \(s32\[\][^,]*, " + re.escape(state))
    found, busy = 0.0, 0.0
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        ops = trace.clip(d.ops, t0, t1)
        found += trace.busy_seconds([
            e for e in ops if scan.search(e[0]) or KERNELS.search(e[0])
            or (not CONTROL.search(e[0]) and (holds(e[0], chunk_tensor)
                                              or holds(e[0], state_tensor)))])
        busy += trace.busy_seconds(ops)
    return (found, busy) if found else None


def read(ctx):
    found = seconds(ctx)
    return None if found is None else 100.0 * found[0] / found[1]
