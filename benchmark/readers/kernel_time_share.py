"""Device time of the events whose names match, over device busy time."""

import re

from benchmark.readers import trace


def read(ctx, pattern: str):
    cuts = ctx.cut()
    if cuts is None:
        return None
    rx, kernel, busy = re.compile(pattern), 0.0, 0.0
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        ops = trace.clip(d.ops, t0, t1)
        kernel += sum(dur for n, _, dur in ops if rx.search(n))
        busy += trace.busy_seconds(ops)
    if kernel == 0.0:
        return None
    return 100.0 * kernel / busy
