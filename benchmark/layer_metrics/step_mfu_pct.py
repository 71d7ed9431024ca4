"""compiled step: operations the served samples need (vbench/flops.py) over
the step executables' device time and the chip's bf16 peak, over the
batches whose results the window saw. Padding rows are not work."""
from vbench import spans


def read(ctx):
    step = spans.step_seconds(ctx)
    if not step:
        return None
    by_id = {c[1]: c for c in ctx["cams"]}
    total = 0
    for b in spans.batches(ctx["stage"]):
        for dev in b["devices"]:
            _, _, role, h, wd, _ = by_id[dev]
            m = ctx["model_of"][ctx["role_model"][role]]
            total += ctx["flops"].sample_flops(m["family"], m["sizes"], h, wd)
    if not total:
        return None
    return 100.0 * total / step / ctx["peak"]["bf16_flops_per_s"]
