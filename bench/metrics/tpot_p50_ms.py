"""Median over finished requests of the mean gap between output tokens."""

from benchlib import readers


def read(ctx):
    return readers.tpot_ms(ctx, 50)
