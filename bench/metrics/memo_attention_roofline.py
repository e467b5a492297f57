"""memo_attention's share of its roofline: the least time of the work the
window gave it over the device time of the kernel's events in the trace.

The work (``work/memo_attention.py``) is summed per batch served: every
padded row of the batch, filler rows included, at the batch's bucket
length, split into hit and miss rows by that batch's own hit share (the
server's counters around its ``step()``), once per memoized layer. The
kernel's events carry the name of the jitted function around its
``pallas_call`` (``_memo_attention_pallas``); the kernel body's own name
(``_memo_kernel``) is matched too. Silent where the kernel never ran.
"""
from bench import tracefold

KERNEL = ("_memo_kernel", "_memo_attention_pallas")


def read(ctx):
    if ctx.trace is None:
        return None
    t, n = tracefold.kernel_s(ctx.trace, KERNEL)
    if t <= 0:
        return None
    work = ctx.work("memo_attention")
    rows, layers = ctx.counters["rows_per_batch"], ctx.counters["n_memo_layers"]
    ops = nbytes = 0.0
    for _, _, _, bucket, hits, attempts in ctx.window.steps:
        hit = hits / attempts if attempts else 0.0
        o, b = work.work(ctx.model, bucket, rows * hit, rows * (1 - hit),
                         ctx.store["codec"])
        ops += o * layers
        nbytes += b * layers
    t_ops = ops / ctx.peaks["bf16_flops_per_s"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.log(f"[roofline] memo_attention: {n} events, {t:.6f} s; least "
            f"time {max(t_ops, t_bytes):.6f} s, bound by "
            f"{'operations' if t_ops >= t_bytes else 'bytes'}")
    return 100.0 * max(t_ops, t_bytes) / t
