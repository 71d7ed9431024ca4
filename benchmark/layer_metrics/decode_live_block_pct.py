"""stream head: of the key blocks a pass over every position a stream could
hold in the cache would fetch in a batch's decode attention (all ``cap``
cacheable positions, in every decode call of the round), the share the
attention fetched (``decode_blocks_live`` / ``decode_blocks_dense``, the
step's own counts from each stream's depth: the key blocks that start
before its context's end), median per batch. What the decode attention's
reads from the cache scale with; ~60 over a fleet whose depths are spread
evenly, 100 from an attention that reads everything. None where the
program's batches carry no such fields (a head without this attention, the
parent commit)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(
        ctx["stage"],
        lambda b: 100.0 * b["decode_blocks_live"] / b["decode_blocks_dense"])
