"""Whole decode step's share of the chip's bf16 peak in the traced slice:
model FLOPs of the tokens decoded in the slice (two per weight of every
layer and of the output head, plus attention over the live keys) over the
device time of the decode-step programs, over the peak.  A decode step is
a device program that runs the paged decode kernel; in this cell the
slice is mostly forks, so the step's own time is the base, not the
slice's."""

from benchlib import cost, readers, stats

# The paged decode-attention kernel in a TPU trace, by its signature: a
# custom call that computes one query row per slot and takes the engine's
# page table (int32, one row per slot) as its first operand.  No prefill
# kernel has that signature.
KERNEL = r"^\S+ = \w+\[{rows},[^\]]*\]\S* custom-call\(s32\[{rows},\d+\]"


def read(ctx):
    steps = readers.step_programs(ctx, KERNEL)
    pos = stats.decode_tokens_in(ctx.records, *ctx.slice_s)
    if not steps or not pos:
        return None
    seconds = sum(e - s for _, s, e in steps) * 1e-9
    flops = cost.decode_token_flops(ctx.cfg, pos)
    return 100.0 * flops / seconds / ctx.peak["bf16_flops"]
