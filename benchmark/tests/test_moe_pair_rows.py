"""The reader of ``moe_pair_rows_pct`` (``layer_metrics/moe_pair_rows_pct.py``)
on hand-written stage traces: per batch the rows the step's expert layers
moved over the pairs they were routed, the median over batches; 100 where
every call ran its top rung; nothing from a program whose batches carry no
such count (the parent commit)."""

import pytest
from vbench import loader

# a round of dsv2_64_360p: 16 prefill chunks x 4 routed layers of 18,816
# pairs, and 8 decode steps x 4 layers of 384
ROUTED = 16 * 4 * 18816 + 8 * 4 * 384


def _records(tick, moved=None, n=3):
    """Stage records of one batch of ``n`` results."""
    fields = {} if moved is None else {"moe_pair_rows": moved,
                                       "moe_pairs_routed": ROUTED}
    return [dict(fields, tick=tick, batch=(tick, 0), t_emitted=1_000.0 + tick)
            for _ in range(n)]


def _read(stage):
    return loader.layer_metric("moe_pair_rows_pct").read({"stage": stage})


@pytest.mark.parametrize("moved,pct", [
    (ROUTED, 100.0),
    # every prefill call on its smallest rung (2,432 rows), decode whole
    (16 * 4 * 2432 + 8 * 4 * 384, 100.0 * (16 * 4 * 2432 + 8 * 4 * 384)
     / ROUTED),
    # half the calls on the smallest rung, half on the next (4,736)
    (8 * 4 * (2432 + 4736) + 8 * 4 * 384, 100.0 * (8 * 4 * (2432 + 4736)
                                                   + 8 * 4 * 384) / ROUTED),
], ids=["top-rung", "smallest-rung", "two-rungs"])
def test_it_is_rows_moved_over_pairs_routed(moved, pct):
    assert _read(_records(1, moved)) == pytest.approx(pct)


def test_it_is_the_median_over_batches():
    stage = (_records(1, ROUTED // 8) + _records(2, ROUTED // 4)
             + _records(3, ROUTED, n=7))
    assert _read(stage) == pytest.approx(25.0)
    # a batch without the count is left out
    assert _read(_records(1) + _records(2, ROUTED // 2)) \
        == pytest.approx(50.0)


def test_a_program_without_the_counter_reads_nothing():
    assert _read(_records(1) + _records(2)) is None
    assert _read([]) is None
    assert _read([{"device_id": "cam0", "t_emitted": 1.0}]) is None
