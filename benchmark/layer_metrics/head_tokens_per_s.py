"""stream head: tokens through the head (visual tokens prefilled plus tokens
decoded, from each batch's trace: ``head_prefill_tokens``, ``n`` results x
``head_decode_steps``) over the window's seconds. None where the program's
batches carry no such fields."""
from vbench import batch_trace


def read(ctx):
    tokens = [b["head_prefill_tokens"] + b["n"] * b["head_decode_steps"]
              for b in batch_trace.batches(ctx["stage"])
              if "head_prefill_tokens" in b]
    if not tokens or not ctx["seconds"] > 0:
        return None
    return sum(tokens) / ctx["seconds"]
