"""1 - (union of the device's operation intervals) / (traced interval), the
mean over the chips."""

from benchmark.readers import trace


def read(ctx):
    cuts = ctx.cut()
    if cuts is None:
        return None
    shares = [1.0 - trace.busy_seconds(trace.clip(d.ops, t0, t1)) / (t1 - t0)
              for d, (t0, t1, _) in zip(ctx.devices, cuts)]
    return 100.0 * sum(shares) / len(shares)
