"""Whole decode step's share of the chip's bf16 peak in the traced slice:
model FLOPs of the tokens decoded in the slice (two per weight of every
layer and of the output head, plus attention over the live keys) per
second of slice, over the peak."""

from benchlib import cost, stats


def read(ctx):
    if ctx.slice_s is None:
        return None
    lo, hi = ctx.slice_s
    pos = stats.decode_tokens_in(ctx.records, lo, hi)
    if not pos:
        return None
    flops = cost.decode_token_flops(ctx.cfg, pos)
    return 100.0 * flops / (hi - lo) / ctx.peak["bf16_flops"]
