"""stream head: drafts of the prediction module that the decode loop
accepted, as a share of those it verified (``mtp_accepted`` /
``mtp_drafted``, the step's own counts over a batch's streams), median per
batch. With weights drawn from a seed the module is right about once in the
held vocabulary: the cell reads ~0 and runs D iterations a round; the
number moves when the weights are trained ones or the drafting changes.
None where the program's batches carry no such fields."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(
        ctx["stage"],
        lambda b: 100.0 * b["mtp_accepted"] / max(b["mtp_drafted"], 1))
