"""compiled step: device time of the step executables per round of the
fleet (trace), over the rounds whose results the window saw."""
from vbench import spans


def read(ctx):
    step = spans.step_seconds(ctx)
    rounds = spans.rounds(ctx["stage"])
    if step is None or not rounds:
        return None
    return step / rounds * 1000.0
