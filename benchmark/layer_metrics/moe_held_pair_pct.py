"""expert layer: of the (token, expert) pairs a batch's routers chose over
ALL experts, the share that fell on the experts held here
(``moe_pairs_local`` / ``moe_pairs_total``, the step's own counts over a
batch's prefill and decode), median per batch. An even router gives held /
routed: 6.25 at 10 of 160. It is what a held expert's slots, and with them
the grouped matmul's time, scale with. None where the program's batches
carry no ``moe_pairs_total`` (a head that does not count it, the parent
commit)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(
        ctx["stage"],
        lambda b: 100.0 * b["moe_pairs_local"] / b["moe_pairs_total"])
