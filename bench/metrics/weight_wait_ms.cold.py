"""Time a fork's prefill waits on the host for its weights: the median over
the forks that start in the traced slice of the summed
``tidal.stream.wait`` spans of their request, the time its consumer
blocked until the streamer thread handed a slice over.  A transfer still in
flight after the hand-over is waited for by the device, not the host: that
shows as idle time under the request's prefill spans, not here."""

from benchlib import program


def read(ctx):
    return program.read(ctx, program.weight_wait_ms)
