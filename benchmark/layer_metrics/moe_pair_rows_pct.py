"""expert layer: the rows a batch's expert layers moved (the capacity rung
each call's held-pair count chose) over the (token, expert) pairs they were
routed (``moe_pair_rows`` / ``moe_pairs_routed``, the step's own counts over
a batch's prefill and decode), median per batch. 100 where every call moved
all N x k pairs; how far under it says how often the small rungs engage.
None where the program's batches carry no such count (the parent commit)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(
        ctx["stage"],
        lambda b: 100.0 * b["moe_pair_rows"] / b["moe_pairs_routed"])
