"""Median over requests of (start of the step() span that served it -
scheduled arrival): time a request waited for the runtime's batcher."""
import statistics


def read(ctx):
    w = [s.step_start - s.arrival for s in ctx.window.served.values()]
    return 1e3 * statistics.median(w) if w else None
