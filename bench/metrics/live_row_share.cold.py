"""Percent of the rows the traced slice's decode steps computed that held
a decoding request: the sum of ``live`` over the sum of ``rows`` of their
``tidal.step`` spans."""

from benchlib import program


def read(ctx):
    return program.read(ctx, program.live_row_share)
