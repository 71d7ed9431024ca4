"""What a serving step carries from round to round sits behind one seam
(ISSUE 32): one slot allocator under the four per-stream pools
(``engine/stream_state.py`` ``SlotMap``), one surface the three pools a
step takes in and gives back answer ``InferenceEngine._dispatch`` through
(``carry`` -> ``Carry``), and ``_step``'s donated positions reckoned from
what those states declare. CPU backend, tiny shapes: results and counts."""

import jax
import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine import InferenceEngine
from video_edge_ai_proxy_tpu.engine.stream_state import (
    ClipWindowPool, SlotMap, StreamStatePool, _ThumbPool)
from video_edge_ai_proxy_tpu.models import registry as models
from video_edge_ai_proxy_tpu.temporal.state_pool import TrackStatePool
from video_edge_ai_proxy_tpu.utils.config import EngineConfig

GEOM = (8, 8, 3)
HEAD = "tiny_videomae_lfm2"


# -- the slot map, through each pool's own way of handing slots out ----------

def _thumb_slots():
    pool = _ThumbPool(4)
    zeros = np.zeros((1, 4, 4), np.float32)
    return (lambda key: (pool.scatter([key], zeros), pool._slots[key])[1],
            pool.pop)


def _head_slots():
    spec = models.get(HEAD)
    pool = StreamStatePool(spec.build(), grow=8)
    return (lambda key: int(pool.plan([key], 1)["idx"][0]), pool.pop)


def _track_slots():
    pool = TrackStatePool(side=4, clip_len=2)
    return pool._row_for, pool.pop


def _window_slots(geom):
    def make():
        pool = ClipWindowPool(2, (1, 2, 4, 8))
        return (lambda key: int(pool.plan([key], geom, 1)["idx"][0]),
                pool.pop)
    return make


@pytest.mark.parametrize("make, first", [
    (_thumb_slots, 1), (_head_slots, 0), (_track_slots, 1),
    (_window_slots(GEOM), 0)],
    ids=["thumbs", "head_state", "track_tiles", "clip_windows"])
def test_every_pool_hands_out_slots_in_its_present_order(make, first):
    """First slots count up from 0, or from 1 where row 0 is the pool's
    zero row; a freed slot goes to the next new owner, the last freed
    first; an owner keeps its slot."""
    take, pop = make()
    assert [take(k) for k in "abcd"] == [first + i for i in range(4)]
    assert take("b") == first + 1                   # an owner keeps its slot
    pop("a"), pop("c")
    pop("stranger")                                 # frees nothing
    assert take("e") == first + 2                   # LIFO: c's, then a's
    assert take("f") == first
    assert take("g") == first + 4                   # none free: the next new


def test_clip_windows_keep_a_free_list_a_geometry():
    pool = ClipWindowPool(2, (1, 2, 4))
    other = (4, 4, 3)
    slot = lambda key, geom: int(  # noqa: E731
        pool.plan([key], geom, 1)["idx"][0])
    assert [slot(k, GEOM) for k in "ab"] == [0, 1]
    assert slot("x", other) == 0                    # its own count
    pool.pop("a")
    assert slot("y", other) == 1                    # a's slot is not other's
    assert slot("c", GEOM) == 0
    assert slot("b", other) == 2                    # b moves: leaves GEOM's 1
    assert slot("d", GEOM) == 1


def test_the_slot_map_is_dict_like_for_the_gc():
    slots = SlotMap(first=1)
    assert not slots and len(slots) == 0 and slots.high == 1
    assert slots.take("a") == (1, True) and slots.take("a") == (1, False)
    assert slots.take("b") == (2, True) and slots.high == 3
    assert "a" in slots and "z" not in slots and set(slots) == {"a", "b"}
    for key in slots:                               # a copy: pop while walking
        assert slots.pop(key) in (1, 2)
    assert slots.pop("a", "gone") == "gone" and not slots
    assert slots.high == 3                          # the mark stays


# -- the carried states' one surface ------------------------------------------

class _Thumbs:
    ids, bucket, geom = ["a", "b"], 4, GEOM
    grow_by = 64                # past the first 64 rows

    def __init__(self):
        self.state = _ThumbPool(4)
        self.restarts = []

    def outputs(self, args):
        (prev,) = args
        assert prev.shape == (self.bucket, 4, 4)    # a gather, not the pool
        return {"quality_thumbs": prev + 1.0, "top_ids": 0}

    def slot(self, key):
        return self.state._slots.get(key)

    def buffer(self):
        return self.state._pool

    def after_loss(self):
        # never donated: nothing was lost, the rows stand
        assert self.slot("a") == 1 and self.state._pool is not None


class _Head:
    ids, bucket, geom = ["a", "b"], 4, GEOM
    grow_by = 2

    def __init__(self):
        self.rounds = []
        self.state = StreamStatePool(
            models.get(HEAD).build(), grow=2,
            note_round=lambda *counts: self.rounds.append(counts))

    def outputs(self, args):
        state, idx, pos0, reset, rounds = args
        assert state is self.state.state
        for vec in (idx, pos0, reset, rounds):
            assert vec.shape == (self.bucket,)
        return {"state": jax.tree.map(lambda a: a + 0, state), "tokens": 0}

    def slot(self, key):
        return self.state._slots.get(key)

    def buffer(self):
        return self.state.state

    def after_loss(self):
        assert len(self.state) == 0 and self.state.nbytes() == 0


class _Window:
    ids, bucket, geom = ["a", "b"], 4, GEOM
    grow_by = 3                 # 2 streams hold bucket 2, 5 need bucket 8

    def __init__(self):
        self.restarts = []
        self.state = ClipWindowPool(
            2, (1, 2, 4, 8),
            note_restart=lambda reason, n: self.restarts.append((reason, n)))

    def outputs(self, args):
        window, idx, pos = args
        assert window is self.state.window(GEOM)
        assert window.shape == (self.state.capacity(GEOM), 2) + GEOM
        assert idx.shape == pos.shape == (self.bucket,)
        return {"window": window + 0, "top_ids": 0}

    def slot(self, key):
        st = self.state._streams.get(key)
        return st and st[1]

    def buffer(self):
        return self.state.window(GEOM)

    def after_loss(self):
        # every stream of the geometry that held a frame: a, s0..s2, new
        assert self.restarts == [("step_error", 5)]
        assert self.state.held("a") == 0 and self.slot("a") == 0


@pytest.mark.parametrize("kind", [_Thumbs, _Head, _Window],
                         ids=["thumbs", "head_state", "clip_window"])
def test_a_carried_state_answers_dispatch_through_one_surface(kind):
    k = kind()
    state = k.state
    assert state.nbytes() == 0 and not state and len(state) == 0
    assert state.key and state.ledger
    assert all(0 <= i < state.step_args for i in state.donated)

    # plan -> the declared number of arguments, the buffer first -> commit
    # takes the buffer back under the state's key and leaves the rest
    carry = state.carry(k.ids, k.bucket, None, k.geom)
    args = carry.args()
    assert len(args) == state.step_args
    outputs = k.outputs(args)
    handed = outputs[state.key]
    rest = set(outputs) - {state.key}
    carry.commit(outputs)
    assert set(outputs) == rest
    assert set(state) == set(k.ids) and bool(state)
    if state.donated:
        assert k.buffer() is handed                 # the same memory, kept
    small = state.nbytes()
    assert small > 0

    # a second round: the same slots
    slots = {d: k.slot(d) for d in k.ids}
    carry = state.carry(k.ids, k.bucket, None, k.geom)
    carry.commit(k.outputs(carry.args()))
    assert {d: k.slot(d) for d in k.ids} == slots

    # growth shows in the ledger's bytes
    more = k.ids + [f"s{i}" for i in range(k.grow_by)]
    bucket = 1 << (len(more) - 1).bit_length()
    k.bucket = bucket
    carry = state.carry(more, bucket, None, k.geom)
    carry.commit(k.outputs(carry.args()))
    assert state.nbytes() > small and len(state) == len(more)

    # pop frees the slot and the next owner gets it
    freed = k.slot("b")
    state.pop("b", None)
    assert "b" not in set(state) and state.pop("b", "gone") == "gone"
    carry = state.carry(["new"], bucket, None, k.geom)
    carry.commit(k.outputs(carry.args()))
    assert k.slot("new") == freed

    # the step raised after its buffers were donated: the state is
    # servable at once, and what it lost is counted
    carry = state.carry(k.ids[:1], bucket, None, k.geom)
    carry.args()
    carry.lost()
    k.after_loss()
    carry = state.carry(k.ids, bucket, None, k.geom)
    args = carry.args()
    assert len(args) == state.step_args
    carry.commit(k.outputs(args))
    assert set(k.ids) <= set(state) and state.nbytes() > 0


def test_a_head_counts_its_round_at_the_commit_and_resets_after_a_loss():
    k = _Head()
    c = k.state.cfg
    carry = k.state.carry(["a", "b"], 4, None, None)
    assert carry.wait is not None and carry.emit is None and carry.step is None
    assert carry.trace["head_prefill_tokens"] == 2 * c.visual_tokens
    assert carry.trace["head_resets"] == 2 and carry.trace["pool_s"] >= 0
    assert k.rounds == []                           # not before the commit
    carry.commit(k.outputs(carry.args()))
    assert k.rounds == [(2 * c.visual_tokens, 2 * c.decode_steps, 2)]
    carry = k.state.carry(["a", "b"], 4, None, None)
    assert carry.trace["head_resets"] == 0
    carry.lost()
    carry = k.state.carry(["a", "b"], 4, None, None)
    assert carry.trace["head_resets"] == 2          # every stream anew
    carry.wait()                                    # new buffers: returns


def test_a_window_names_the_program_and_the_rows_left_to_emit():
    named = []

    def program(geom, bucket, slots, write_only):
        named.append((geom, bucket, slots, write_only))
        return "write" if write_only else "step"

    pool = ClipWindowPool(2, (1, 2, 4), program=program)
    carry = pool.carry(["a", "b"], 2, None, GEOM)
    # no window is full: the write alone, nothing owed
    assert carry.step == "write" and carry.emit == []
    assert carry.trace == {"window_rows": 2} and carry.wait is None
    carry = pool.carry(["a", "c"], 2, None, GEOM)
    assert carry.step == "step" and carry.emit == [0]       # a's is full
    carry = pool.carry(["a", "c"], 2, None, GEOM)
    assert carry.step == "step" and carry.emit is None      # all: no narrowing
    assert named == [(GEOM, 2, 2, True), (GEOM, 2, 4, False),
                     (GEOM, 2, 4, False)]


# -- _step reckons the donated positions from what the states declare --------

@pytest.fixture
def jit_calls(monkeypatch):
    calls = []
    real = jax.jit

    def spy(fn, *args, **kw):
        calls.append(kw.get("donate_argnums"))
        return real(fn, *args, **kw)

    monkeypatch.setattr(jax, "jit", spy)
    return calls


@pytest.mark.parametrize("model, quality, window, want", [
    ("tiny_vit", False, 0, ()),                     # plain
    ("tiny_vit", True, 0, ()),                      # thumbnails: a gather
    ("tiny_videomae", True, 4, (2,)),               # window
    (HEAD, True, 0, (2,)),                          # head
    (HEAD, True, 4, (2, 5)),                        # window + head
], ids=["plain", "thumbnails", "window", "head", "window_head"])
def test_step_donates_what_its_carried_states_declare(
        jit_calls, model, quality, window, want):
    eng = InferenceEngine(MemoryFrameBus(), EngineConfig(
        model=model, batch_buckets=(4,), ladder=False, quality=quality))
    eng.warmup()
    states = eng._carried(model, bool(window))
    assert [type(s) for s in states] == (
        [ClipWindowPool] if window else []) + (
        [StreamStatePool] if model == HEAD else []) + (
        [_ThumbPool] if quality and model == "tiny_vit" else [])
    declared, at = (), 2            # after (variables, frames)
    for state in states:
        declared += tuple(at + i for i in state.donated)
        at += state.step_args
    assert declared == want
    del jit_calls[:]
    eng._step((8, 8), 4, model, **({"window": window} if window else {}))
    assert jit_calls == [want]
    # a canvas group carries no thumbnails (its rows are no stream's)
    assert _ThumbPool not in [
        type(s) for s in eng._carried(model, bool(window), canvas=True)]


def test_the_gc_and_the_hbm_ledger_read_one_list():
    eng = InferenceEngine(MemoryFrameBus(), EngineConfig(
        model="tiny_videomae", batch_buckets=(2,), ladder=False, hbm=True))
    eng.warmup()
    fixed = len(eng._per_stream)
    assert eng._thumbs in eng._per_stream
    wpool, head = eng._window_pool("tiny_videomae"), eng._head_pool(HEAD)
    assert eng._head_pool("tiny_videomae") is None      # no head state
    assert eng._per_stream[fixed:] == [wpool, head]
    assert eng._window_pool("tiny_videomae") is wpool   # built once
    wpool.ensure(GEOM, 2)
    head.ensure(2)
    pools = eng.hbm.pools()["pools"]
    assert pools["clip_windows"]["bytes"] == wpool.nbytes() > 0
    assert pools["stream_state"]["bytes"] == head.nbytes() > 0
    assert pools["thumbs"]["bytes"] == 0
    # a departed stream leaves every container in one loop
    wpool.plan(["gone"], GEOM, 2)
    head.plan(["gone"], 2)
    eng._window_home["gone"] = "tiny_videomae"
    eng._ann_state["gone"] = {}
    for container in eng._per_stream:
        container.pop("gone", None)
    assert not any(eng._per_stream)
