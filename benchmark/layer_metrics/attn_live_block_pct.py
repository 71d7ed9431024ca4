"""stream head: of the tiles (a key block against a lane tile of 128
queries) a pass over every key a stream could hold would visit in a batch's
prefill attention (all ``cap`` cacheable positions and the round's own,
whole), the share the attention visited (``attn_blocks_live`` /
``attn_blocks_dense``, the step's own counts from each stream's depth: the
key blocks that start before its context's end, and the new rows' blocks
against the queries that can see them), median per batch. What the
attention's arithmetic scales with; ~59 over a fleet whose depths are spread
evenly, 100 from an attention that visits everything. None where the
program's batches carry no such fields (a head without this attention, the
parent commit)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(
        ctx["stage"],
        lambda b: 100.0 * b["attn_blocks_live"] / b["attn_blocks_dense"])
