"""The reader of ``decode_live_block_pct``
(``layer_metrics/decode_live_block_pct.py``) on a hand-written stage trace:
per batch the step's count of the key blocks its decode attention fetched
over what a dense pass would fetch, the median over batches; nothing from a
program whose batches carry no such fields."""

import pytest
from vbench import loader


def _records(tick, live=None, dense=None, n=3):
    """Stage records of one batch of ``n`` results."""
    fields = {} if live is None else {"decode_blocks_live": live,
                                      "decode_blocks_dense": dense}
    return [dict(fields, tick=tick, batch=(tick, 0), t_emitted=1_000.0 + tick)
            for _ in range(n)]


def _read(stage):
    return loader.layer_metric("decode_live_block_pct").read({"stage": stage})


def test_it_is_the_median_over_batches_of_live_over_dense():
    # 64 streams x 5 attentions x 8 iterations: 13 key blocks a stream dense
    dense = 64 * 5 * 8 * 13
    stage = (_records(1, dense // 2, dense) + _records(2, dense // 4, dense)
             + _records(3, dense, dense, n=7))
    assert _read(stage) == pytest.approx(50.0)


def test_a_batch_without_the_fields_is_left_out():
    stage = _records(1) + _records(2, 30, 40) + _records(3)
    assert _read(stage) == pytest.approx(75.0)


def test_a_program_without_the_counter_reads_nothing():
    # the parent commit, and the grouped-query heads, carry no such fields
    assert _read(_records(1) + _records(2)) is None
    assert _read([]) is None
    assert _read([{"device_id": "cam0", "t_emitted": 1.0}]) is None
    prefill_only = [dict(r, attn_blocks_live=30, attn_blocks_dense=40)
                    for r in _records(1)]
    assert _read(prefill_only) is None
