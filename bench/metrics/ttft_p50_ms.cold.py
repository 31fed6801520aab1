"""Median time to first token over the window's finished requests, each of
which forks and streams its weights: the cold start, on the host clock."""

from benchlib import readers


def read(ctx):
    return readers.ttft_ms(ctx, 50)
