"""The whole step's share of the chip's bf16 peak: memo-off model
operations of the requests served in the window, at their own lengths,
over the time inside the driver's step() spans in the trace times the
peak. Bounds every kernel's roofline from above in its effect on
latency."""
from bench import tracefold


def read(ctx):
    if ctx.trace is None:
        return None
    steps = tracefold.spans_of(ctx.trace, "bench.step")
    t = sum(e - s for s, e in steps) * 1e-9
    if t <= 0:
        return None
    work = ctx.work("model_step")
    ops = sum(work.flops(ctx.model, s.tokens, ctx.config["head"])
              for s in ctx.window.served.values())
    return 100.0 * ops / (t * ctx.peaks["bf16_flops_per_s"])
