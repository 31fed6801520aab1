"""Milliseconds of tracing, lowering and compiling per fork in the window:
the seconds of the program's compile counter (``FaaSRuntime.stats()
["compile"]``, all three of JAX's compile events) over the window, per
request of the window that forked an engine."""


def read(ctx):
    delta = getattr(ctx, "counters", None)
    forks = sum(r["kind"] in ("fork", "cold") for r in ctx.records)
    if delta is None or not forks:
        return None
    return 1e3 * sum(s for _, s in delta.values()) / forks
