"""``moe_combine_ms``: the prefill's way back from the expert layer's
sorted held rows to the tokens, read from the stage map's ``moe_combine``
paths outside the decode loop; silent for a program whose map has no such
path. Counts only, never a time."""

import pytest

from vbench import loader, stage_trace


def _ctx(ticks):
    """A run whose reduced ticks gave ``ticks``: [{scope path: ms}]."""
    return {stage_trace._KEY: [{k: v / 1e3 for k, v in t.items()}
                               for t in ticks]}


def test_it_reads_the_prefill_way_back_and_is_silent_without_it():
    read = loader.layer_metric("moe_combine_ms").read
    tick = {("head_prefill", "head_moe", "moe_combine"): 12.0,
            ("head_prefill", "head_moe"): 30.0,
            ("head_prefill", "head_moe", "moe_experts"): 5.0,
            ("head_decode", "head_moe", "moe_combine"): 1.5}
    assert read(_ctx([tick, dict(tick), {
        ("head_prefill", "head_moe", "moe_combine"): 20.0}])) == \
        pytest.approx(12.0)
    # the parent's program: the way back lay in the bare ``head_moe``
    assert read(_ctx([{("head_prefill", "head_moe"): 42.0}])) is None
    assert read({stage_trace._KEY: None}) is None
