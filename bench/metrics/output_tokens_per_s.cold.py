"""Output tokens that reached the host inside the window, per second of
window, where every request forks: one caller's whole cycle of fork,
prefill and decode.  A request straddling an edge counts its tokens by its
first-token and completion times, spread evenly in between."""

from benchlib import stats


def read(ctx):
    return stats.tokens_in(ctx.records, 0.0, ctx.window_s) / ctx.window_s
