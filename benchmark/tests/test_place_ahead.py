"""The reader of ``place_ahead_ms`` (``layer_metrics/place_ahead_ms.py``) on
a hand-written stage trace: per tick the sum over its batches of how long
before ``t_collect`` each was handed to the transfer thread, the median
over ticks; 0.0 from a program that hands over after the collect, nothing
from one without the stamps."""

import pytest
from vbench import loader

T = 1_000.0     # wall stamps: seconds, far from 0


def _records(tick, ahead_s, n=2, t_collect=0.1):
    """Stage records of one tick: batch i was handed over ``ahead_s[i]``
    before the tick's ``t_collect`` (after it where negative; None: no
    placement stamp), and has ``n`` results."""
    out = []
    for g, ahead in enumerate(ahead_s):
        fields = {"t_collect": T + tick + t_collect}
        if ahead is not None:
            fields["t_place_q"] = fields["t_collect"] - ahead
        out += [dict(fields, tick=tick, batch=(tick, g),
                     t_emitted=T + tick + 0.5) for _ in range(n)]
    return out


def _read(stage):
    return loader.layer_metric("place_ahead_ms").read({"stage": stage})


def test_it_sums_a_ticks_batches_and_takes_the_median_over_ticks():
    # tag batch handed over 48 ms before the collect closed, clip batch 2 ms
    stage = (_records(3, (0.048, 0.002)) + _records(4, (0.040, 0.002))
             + _records(5, (0.055, 0.001), n=7))
    assert _read(stage) == pytest.approx(50.0)


def test_a_batch_handed_over_after_the_collect_counts_zero():
    # the parent's order: t_collect <= t_place_q, whatever the distance
    stage = _records(1, (-0.007, -0.110)) + _records(2, (-0.004,)) \
        + _records(3, (-0.009, -0.100))
    assert _read(stage) == 0.0
    # and a tick's late batch takes nothing from its early one
    assert _read(_records(1, (0.030, -0.050))) == pytest.approx(30.0)


def test_a_program_without_the_stamps_reads_nothing():
    assert _read(_records(1, (None,)) + _records(2, (None, None))) is None
    assert _read([]) is None
    assert _read([{"device_id": "cam0", "t_emitted": 1.0}]) is None
