"""Paged decode-attention kernel's share of its roofline in the traced
slice: the least time the chip could take for the operations and bytes
the live rows need (``benchlib.cost.paged_decode``), over the kernel's
device time.  Standard error says which bound applies."""

import sys

from benchlib import cost, readers, stats

# The paged decode-attention kernel in a TPU trace, by its signature: a
# custom call that computes one query row per slot and takes the engine's
# page table (int32, one row per slot) as its first operand.  No prefill
# kernel has that signature.
KERNEL = r"^\S+ = \w+\[{rows},[^\]]*\]\S* custom-call\(s32\[{rows},\d+\]"


def read(ctx):
    calls, ev = readers.decode_calls(ctx, KERNEL)
    if not calls:
        return None
    seconds = sum(e - s for _, s, e in ev) * 1e-9
    work = cost.paged_decode(
        ctx.cfg, stats.decode_tokens_in(ctx.records, *ctx.slice_s),
        round(calls), ctx.cell["n_slots"], ctx.runtime["page_size"],
        kv_itemsize=ctx.runtime["kv_itemsize"])
    share, bound = cost.roofline_share(work, seconds, ctx.peak)
    print(f"paged_decode_roofline: {len(ev)} kernel calls, {seconds:.4f}s, "
          f"{work['flops']:.4g} FLOP, {work['bytes']:.4g} B, bound by "
          f"{bound}", file=sys.stderr)
    return share
