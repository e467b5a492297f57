"""Share of the time inside the driver's step() spans during which no
operation ran on the device, from the profiler trace."""
from bench import tracefold


def read(ctx):
    if ctx.trace is None:
        return None
    busy, total = tracefold.busy_in_spans(
        ctx.trace, tracefold.spans_of(ctx.trace, "bench.step"))
    if total <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / total)
