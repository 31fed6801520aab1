"""Percent of the finished requests' prompt tokens served from a reused
prefix in the paged arena (the runtime's ``reused_prefix_len``)."""

from benchlib import stats


def read(ctx):
    done = stats.done(ctx.records)
    total = sum(r["prompt_len"] for r in done)
    if not total:
        return None
    return 100.0 * sum(r["reused"] for r in done) / total
