"""compiled step: device self time of the encoder and the classifier
(``embed``, ``encoder_block``, ``cls_head``, ``softmax_topk``; a stream
head's encoder runs inside its prefill loop), per tick
(vbench/stage_trace.py)."""
from vbench import stage_trace


def read(ctx):
    return stage_trace.median_ms(
        ctx, ("embed", "encoder_block", "cls_head", "softmax_topk"))
