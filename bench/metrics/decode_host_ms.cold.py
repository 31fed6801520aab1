"""Host time per decode step after a fork, from the program's spans: the
median over the traced slice's decode steps of (end of this step's
``tidal.decode.sync`` - end of the previous step's - this sync's
duration), the time a step spends on the host beyond waiting for the
device to finish it."""

from benchlib import program


def read(ctx):
    return program.read(ctx, program.decode_host_ms)
