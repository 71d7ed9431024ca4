"""stream head: the state-space scan's share of its roofline. The
``ssm_scan`` scope's device self time per tick (ops/ssd.py: the chunked
scan of the prefill and the decode steps' recurrence; vbench/stage_trace.py)
against the least time the chip could take for it: max(operations / bf16
peak, HBM bytes / HBM bandwidth) of ``benchmark/peaks.json``, the
operations and bytes of one stream's round from its family
(``ssm_scan_ops``, ``ssm_scan_bytes``) times the streams a tick. x 100.
None where no operation of the step carries the scope, or the cell's
family does not count the scan."""
from vbench import batch_trace, loader, stage_trace


def read(ctx):
    ticks = stage_trace.per_tick(ctx)
    if not ticks or ctx.get("peak") is None \
            or not any("ssm_scan" in path for t in ticks for path in t):
        return None
    ms = stage_trace.median_ms(ctx, ("ssm_scan",))
    streams = batch_trace.per_tick(
        ctx["stage"], lambda t: sum(b["n"] for b in t["batches"]))
    counted = [(m, loader.family(m["family"]))
               for m in ctx["model_of"].values()]
    counted = [(m, f) for m, f in counted if hasattr(f, "ssm_scan_ops")]
    if not ms or not streams or len(counted) != 1:
        return None
    m, fam = counted[0]
    tokens = fam._visual_tokens(m["sizes"])
    peak = ctx["peak"]
    least = streams * max(
        fam.ssm_scan_ops(m["sizes"], tokens) / peak["bf16_flops_per_s"],
        fam.ssm_scan_bytes(m["sizes"], tokens) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
