"""95th percentile over the window's requests of how late the open loop
called ``gateway.submit`` after the request's due time: how long a gateway
pump held the loop past a due arrival (benchmark's host clock)."""

from benchlib import stats


def read(ctx):
    return stats.percentile(
        [(r["submit_at"] - r["due"]) * 1e3 for r in ctx.records], 95)
