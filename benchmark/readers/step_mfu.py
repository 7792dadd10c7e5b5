"""The whole step's share of the chip's peak: operations the forward and
backward passes need (``benchmark/flops.py``), times the steps in the traced
interval, over the interval, the chips and the peak."""

from benchmark import flops


def read(ctx):
    cuts = ctx.cut()
    if cuts is None:
        return None
    per_step = flops.train_step_flops(ctx.config, ctx.mix["shape"])
    done = sum(steps for _, _, steps in cuts) / len(cuts) * per_step
    seconds = sum(t1 - t0 for t0, t1, _ in cuts) / len(cuts)
    return 100.0 * done / seconds / (ctx.chips * ctx.peak["bf16_flops_per_s"])
