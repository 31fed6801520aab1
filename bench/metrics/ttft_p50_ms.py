"""Median time to first token over the window's finished requests."""

from benchlib import readers


def read(ctx):
    return readers.ttft_ms(ctx, 50)
