"""Memo-off step time over served step time on the same padded batches,
after the window (host clock around work that ends in
block_until_ready, many batches per side). Above 1 means memoization
pays."""


def read(ctx):
    if not ctx.speedup:
        return None
    t_off, t_memo = ctx.speedup
    return t_off / t_memo
