"""expert layer: of a batch's tokens (a routed layer each: ``moe_pairs_total``
/ ``num_experts_per_tok``), the share whose kept expert groups include a
group held here (``moe_group_hits``, the step's own count), median per
batch. With a group-limited router only these tokens can send this chip a
pair: 37.5 at 3 kept of 8 groups if the groups were chosen evenly. None
where the program's batches carry no such fields, or the configuration no
``num_experts_per_tok``."""
from vbench import batch_trace


def read(ctx):
    k = ctx["cell"]["config"].get("num_experts_per_tok")
    if not k:
        return None
    return batch_trace.per_batch(
        ctx["stage"],
        lambda b: 100.0 * b["moe_group_hits"] * k / b["moe_pairs_total"])
