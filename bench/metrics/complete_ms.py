"""Median over the window's batches of the program's ``memo.complete``
span (``MemoServer._execute``): the split of a served batch into
per-request completions, which for prefill slices every layer's K and V
cache out of the batch for each request and merges them back into the
pytree ``Model.decode_step`` takes. Read from the trace's
``program_spans`` (``spanfold.fold``); silent where it holds no such
span."""
import statistics


def read(ctx):
    if ctx.trace is None:
        return None
    d = [sp[2] - sp[1] for sp in ctx.trace.get("program_spans", ())
         if sp[0] == "memo.complete"]
    return 1e-6 * statistics.median(d) if d else None
