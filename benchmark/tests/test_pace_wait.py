"""The reader of ``pace_wait_ms`` (``layer_metrics/pace_wait_ms.py``) on a
hand-written stage trace: the median per tick, whatever the tick's number
of batches or results, and nothing from a program without the field."""

from vbench import loader


def _records(tick, pace_wait_s, groups=(1,)):
    """Stage records of one tick: ``groups[i]`` results of its batch i."""
    fields = {} if pace_wait_s is None else {"pace_wait_s": pace_wait_s}
    return [dict(fields, tick=tick, batch=(tick, g), t_emitted=10.0 + tick)
            for g, n in enumerate(groups) for _ in range(n)]


def _read(stage):
    return loader.layer_metric("pace_wait_ms").read({"stage": stage})


def test_it_reads_the_median_per_tick():
    # five ticks; the one with two batches and seven results counts once
    stage = (_records(3, 0.020) + _records(4, 0.030, groups=(3, 4))
             + _records(5, 0.0) + _records(6, 0.780) + _records(7, 0.025))
    assert _read(stage) == 25.0


def test_a_tick_that_did_not_wait_reads_zero():
    stage = _records(1, 0.0) + _records(2, 0.0, groups=(2,)) \
        + _records(3, 0.015)
    assert _read(stage) == 0.0


def test_a_program_without_the_field_reads_nothing():
    assert _read(_records(1, None) + _records(2, None)) is None
    assert _read([]) is None
    assert _read([{"device_id": "cam0", "t_emitted": 1.0}]) is None
