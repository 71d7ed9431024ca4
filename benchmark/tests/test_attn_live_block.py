"""The reader of ``attn_live_block_pct``
(``layer_metrics/attn_live_block_pct.py``) on a hand-written stage trace:
per batch the step's count of the tiles its prefill attention visited over
what a dense pass would visit, the median over batches; nothing from a
program whose batches carry no such fields."""

import pytest
from vbench import loader


def _records(tick, live=None, dense=None, n=3):
    """Stage records of one batch of ``n`` results."""
    fields = {} if live is None else {"attn_blocks_live": live,
                                      "attn_blocks_dense": dense}
    return [dict(fields, tick=tick, batch=(tick, 0), t_emitted=1_000.0 + tick)
            for _ in range(n)]


def _read(stage):
    return loader.layer_metric("attn_live_block_pct").read({"stage": stage})


def test_it_is_the_median_over_batches_of_live_over_dense():
    # 64 streams x 5 attentions: 140 tiles a stream dense ((13 + 7) x 7)
    dense = 64 * 5 * 140
    stage = (_records(1, dense // 2, dense) + _records(2, dense // 4, dense)
             + _records(3, dense, dense, n=7))
    assert _read(stage) == pytest.approx(50.0)


def test_a_batch_without_the_fields_is_left_out():
    stage = _records(1) + _records(2, 30, 40) + _records(3)
    assert _read(stage) == pytest.approx(75.0)


def test_a_program_without_the_counter_reads_nothing():
    assert _read(_records(1) + _records(2)) is None
    assert _read([]) is None
    assert _read([{"device_id": "cam0", "t_emitted": 1.0}]) is None
