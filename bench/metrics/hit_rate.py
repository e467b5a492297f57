"""Memo hits over memoized-layer attempts in the window, from the
server's own counters (MemoStats n_hits / n_layer_attempts), differenced
over the window."""


def read(ctx):
    c = ctx.counters
    if not c["n_layer_attempts"]:
        return None
    return 100.0 * c["n_hits"] / c["n_layer_attempts"]
