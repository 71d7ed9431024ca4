"""A metric a test adds: batches dispatched in the window."""
from vbench import spans


def read(ctx):
    return float(len(spans.batches(ctx["stage"]))) or None
