"""Percent of the traced slice with no operation on the device."""

from benchlib import readers


def read(ctx):
    return readers.idle_share(ctx)
