"""Output tokens decoded per batched decode step in the traced slice: the
slice's decoded tokens over the paged decode kernel's calls divided by the
number of layers (each step computes every slot's row)."""

from benchlib import readers, stats

# The paged decode-attention kernel in a TPU trace, by its signature: a
# custom call that computes one query row per slot and takes the engine's
# page table (int32, one row per slot) as its first operand.  No prefill
# kernel has that signature.
KERNEL = r"^\S+ = \w+\[{rows},[^\]]*\]\S* custom-call\(s32\[{rows},\d+\]"


def read(ctx):
    calls, _ = readers.decode_calls(ctx, KERNEL)
    if not calls:
        return None
    return len(stats.decode_tokens_in(ctx.records, *ctx.slice_s)) / calls
