"""Median duration of the driver's span around each server.step() that
served a batch: the engine's prepare_batch -> run_layers -> finalize plus
the runtime's batch assembly and per-request split."""
import statistics


def read(ctx):
    d = [st[1] - st[0] for st in ctx.window.steps]
    return 1e3 * statistics.median(d) if d else None
