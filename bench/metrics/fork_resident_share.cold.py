"""Percent of the forked weight bytes that the template server kept
resident on the device (shared, not streamed), over the window's forks:
sum of ``ForkStats.reused_bytes`` over reused plus streamed bytes."""


def read(ctx):
    forks = [r for r in ctx.records if r["reused_bytes"] + r["streamed_bytes"]]
    total = sum(r["reused_bytes"] + r["streamed_bytes"] for r in forks)
    if not total:
        return None
    return 100.0 * sum(r["reused_bytes"] for r in forks) / total
