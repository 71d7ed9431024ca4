"""expert layer: the prefill's expert layers' share of their roofline. The
``head_moe`` scope's device self time per tick, outside ``head_decode``
(vbench/stage_trace.py), against the least time the chip could take for
the same work: max(operations / bf16 peak, HBM bytes / HBM bandwidth) of
``benchmark/peaks.json``, the operations and bytes of the streams a tick
from the family (``moe_layer_ops``, ``moe_layer_bytes``: the router, W_down
and W_up, the held pairs' two products at an even router's held share, the
shared expert; the weights read once a prefill chunk, the pair rows moved
once each way). x 100. These count the work the layer must do whatever
implements it. None where no operation of the step carries the scope, or
the cell's family does not count the layer."""
from vbench import batch_trace, loader, stage_trace


def read(ctx):
    ticks = stage_trace.per_tick(ctx)
    if not ticks or ctx.get("peak") is None \
            or not any("head_moe" in path for t in ticks for path in t):
        return None
    ms = stage_trace.median_ms(ctx, ("head_moe",), outside=("head_decode",))
    streams = batch_trace.per_tick(
        ctx["stage"], lambda t: sum(b["n"] for b in t["batches"]))
    counted = [(m, loader.family(m["family"]))
               for m in ctx["model_of"].values()]
    counted = [(m, f) for m, f in counted if hasattr(f, "moe_layer_ops")]
    if not ms or not streams or len(counted) != 1:
        return None
    m, fam = counted[0]
    peak = ctx["peak"]
    least = max(
        fam.moe_layer_ops(m["sizes"], streams) / peak["bf16_flops_per_s"],
        fam.moe_layer_bytes(m["sizes"], streams) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
