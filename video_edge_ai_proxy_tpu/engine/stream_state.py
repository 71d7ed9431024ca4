"""Per-stream decoder state on the device: one pool, two kinds of buffer.

The reference keeps no model state at all (it ships frames to external
clients, `/root/reference/README.md:5-27`); a streaming head
(``models/lfm2.py``) carries, for every camera, a fixed short-convolution
state and a growing key-value cache with a length. Precedents here:
``_ThumbPool`` (runner.py) and ``TrackStatePool`` (temporal/state_pool.py),
one kind of fixed tile each.

One :class:`StreamStatePool` a stream-head model: ``conv`` [slots, conv
layers, L-1, d], ``kv`` = (keys, values), each [attention layers, slots, kv
heads, head_dim, max_context], and ``tokens`` [slots, rounds a context x D]
(the ids decoded since the stream's reset), indexed by slot. The serving
step reads and writes by slot index INSIDE the program and the buffers are
donated, so the state never crosses to the host and the key-value cache is
rewritten in place, with no gathered copy. The host keeps what is
deterministic: each stream's slot, length, rounds since its reset and
whether its first context is over. :meth:`plan` turns a batch's device ids
into the step's index, position and reset vectors (the only per-stream host
work, timed as ``pool_s``); a slot is given at first sight and freed when
the stream leaves (``pop``, the tick loop's debounced GC).

Reset policy: a stream resets (zero conv state, length 0, the standing
instruction prefilled first) when the round's visual tokens plus D decoded
ones would pass ``max_context``; and, so that a fleet that starts together
does not reset together, its FIRST context is capped at
``1 + crc32(device_id) % max_rounds`` rounds (``max_rounds`` the rounds a
full context holds), so every round of the fleet holds every depth.

A clip camera's window is device state too (:class:`ClipWindowPool`): one
pool a clip-taking model on the one-device engine (the ``stream`` kind
excepted for now: ``InferenceEngine._window_on_device``), per source
geometry a
buffer ``[slots, clip_len, H, W, C]`` uint8 that the windowed step
(``runner._windowed``) is handed donated, writes the round's new frame
into and reads back in time order, so a frame crosses to the device once
and the ``clip_len - 1`` it joins stay where the step reads them. The host
keeps each stream's slot, write position and fill count. A window's own
slots, not the head's: a window slot is given at a stream's first READ, a
head slot at its first emitted round. A window restarts (fill count 0; a
result again follows the ``clip_len``-th read) whenever a frame the
collector read did not reach it: another geometry or model, a batch shed or
dropped after its frames were read, a step that raised (the donated buffer
is lost: every stream of that geometry). Nothing is seeded from the host.
A mesh engine, and a Collector built without ``device_windows``, keep the
window on the host (``collector._ClipRing``).

All methods run on the tick thread (single writer, as ``_ThumbPool``).
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


def first_context_rounds(device_id: str, mod: int) -> int:
    """Rounds of a stream's first context (de-phased resets)."""
    return 1 + zlib.crc32(device_id.encode()) % max(1, int(mod))


class StreamStatePool:
    __slots__ = ("model", "cfg", "state", "capacity", "_grow", "_slots",
                 "_free", "_len", "_rounds", "_first_left")

    def __init__(self, model, grow: int = 64):
        self.model = model                 # answers ``empty_state(slots)``
        self.cfg = model.cfg               # the round's sizes and policy
        self.state: Optional[dict] = None  # lazy: jax stays off the
        self.capacity = 0                  # control plane (CLAUDE.md)
        self._grow = max(1, int(grow))
        self._slots: Dict[str, int] = {}
        self._free: List[int] = []
        self._len: Dict[str, int] = {}         # positions committed
        self._rounds: Dict[str, int] = {}      # rounds since the reset
        self._first_left: Dict[str, int] = {}  # rounds left of context one

    # dict-like surface for the tick loop's per-stream GC
    def __bool__(self) -> bool:
        return bool(self._slots)

    def __iter__(self):
        return iter(list(self._slots))

    def __len__(self) -> int:
        return len(self._slots)

    def pop(self, device_id: str, default=None):
        """Forget a stream: its slot returns to the free list. Nothing
        reads a freed slot's rows before its next owner's first round,
        which is a reset."""
        slot = self._slots.pop(device_id, None)
        if slot is not None:
            self._free.append(slot)
            self._len.pop(device_id, None)
            self._rounds.pop(device_id, None)
            self._first_left.pop(device_id, None)
        return default

    def ensure(self, slots: int) -> None:
        """Room for ``slots`` streams, grown in steps of ``grow`` (a growth
        changes the step's shapes: its programs compile again)."""
        if slots <= self.capacity and self.state is not None:
            return
        import jax.numpy as jnp

        cap = -(-max(slots, 1) // self._grow) * self._grow
        c = self.cfg
        conv, kv = self.model.empty_state(cap)
        tokens = jnp.full((cap, c.max_rounds * c.decode_steps), -1, jnp.int32)
        if self.state is not None:
            old = self.capacity
            conv = conv.at[:old].set(self.state["conv"])
            kv = tuple(a.at[:, :old].set(b)
                       for a, b in zip(kv, self.state["kv"]))
            tokens = tokens.at[:old].set(self.state["tokens"])
        self.state = {"conv": conv, "kv": kv, "tokens": tokens}
        self.capacity = cap

    def plan(self, device_ids, bucket: int, rows=None) -> dict:
        """The step's vectors for one batch, [bucket] each: ``idx`` the
        slot of each row (``capacity`` for a padded row: its gather is
        clipped and its scatter dropped), ``pos0`` where the round's
        visual tokens start, ``reset``, ``rounds`` since the reset before
        this round. Advances the host's bookkeeping: the round is
        committed when the step is."""
        c = self.cfg
        n_i = len(c.instruction_ids)
        for did in device_ids:
            if did not in self._slots:
                self._slots[did] = (self._free.pop() if self._free
                                    else len(self._slots))
                self._len[did] = 0
                self._rounds[did] = 0
                self._first_left[did] = first_context_rounds(
                    did, c.max_rounds)
        self.ensure(1 + max(self._slots.values(), default=0))
        idx = np.full(bucket, self.capacity, np.int32)
        pos0 = np.full(bucket, n_i, np.int32)
        reset = np.ones(bucket, bool)
        rounds = np.zeros(bucket, np.int32)
        for i, did in enumerate(device_ids):
            r = i if rows is None else rows[i]
            length = self._len[did]
            fresh = (length == 0
                     or length + c.round_positions > c.head.max_context
                     or self._first_left[did] == 0)
            if fresh:
                length = n_i
                self._rounds[did] = 0
                if self._first_left[did] == 0:
                    self._first_left[did] = -1       # context one is over
            if self._first_left[did] > 0:
                self._first_left[did] -= 1
            idx[r], pos0[r], reset[r] = self._slots[did], length, fresh
            rounds[r] = self._rounds[did]
            self._len[did] = length + c.round_positions
            self._rounds[did] += 1
        return {"idx": idx, "pos0": pos0, "reset": reset, "rounds": rounds}

    def wait(self) -> None:
        """Block until the step that last wrote the state has finished."""
        if self.state is not None:
            self.state["tokens"].block_until_ready()

    def nbytes(self) -> int:
        """Device bytes the pool holds (obs/hbm.py ``register_pool``)."""
        if self.state is None:
            return 0
        import jax

        return int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
            self.state)))


class ClipWindowPool:
    """Every clip window of one model on the device (module docstring)."""

    __slots__ = ("clip_len", "_buckets", "_note", "_bufs", "_streams")

    def __init__(self, clip_len: int, buckets: Sequence[int],
                 note_restart: Optional[Callable[[str, int], None]] = None):
        self.clip_len = int(clip_len)
        self._buckets = tuple(sorted(buckets)) or (1,)
        self._note = note_restart or (lambda reason, n: None)
        # geometry (H, W, C) -> {"window": jax.Array or None, "capacity",
        # "free": [slot], "used": slots ever given}
        self._bufs: Dict[tuple, dict] = {}
        # device_id -> [geometry, slot, write position, frames held]
        self._streams: Dict[str, list] = {}

    # dict-like surface for the tick loop's per-stream GC
    def __bool__(self) -> bool:
        return bool(self._streams)

    def __iter__(self):
        return iter(list(self._streams))

    def __len__(self) -> int:
        return len(self._streams)

    def pop(self, device_id: str, default=None):
        """Forget a stream: its slot returns to its geometry's free list.
        The next owner starts at fill count 0 and is not emitted before
        it has rewritten every frame of the slot."""
        st = self._streams.pop(device_id, None)
        if st is not None:
            self._bufs[st[0]]["free"].append(st[1])
        return default

    def _buf(self, geom: tuple) -> dict:
        return self._bufs.setdefault(
            geom, {"window": None, "capacity": 0, "free": [], "used": 0})

    def capacity(self, geom: tuple) -> int:
        buf = self._bufs.get(tuple(geom))
        return buf["capacity"] if buf else 0

    def window(self, geom: tuple):
        """The geometry's buffer, to donate to the step."""
        return self._bufs[tuple(geom)]["window"]

    def put(self, geom: tuple, window) -> None:
        """Take the step's returned buffer back (the same memory)."""
        self._bufs[tuple(geom)]["window"] = window

    def ensure(self, geom: tuple, slots: int) -> None:
        """Room for ``slots`` streams of this geometry: the smallest batch
        bucket that holds them (a fleet that fits one bucket has one
        program a bucket, as without windows), then multiples of the
        largest. A growth keeps the frames held and changes the step's
        shapes: its programs compile again."""
        geom = tuple(geom)
        buf = self._buf(geom)
        if slots <= buf["capacity"] and buf["window"] is not None:
            return
        import jax.numpy as jnp

        top = self._buckets[-1]
        cap = max(buf["capacity"],
                  next((b for b in self._buckets if b >= slots),
                       -(-slots // top) * top))
        new = jnp.zeros((cap, self.clip_len) + geom, jnp.uint8)
        if buf["window"] is not None:
            new = new.at[:buf["capacity"]].set(buf["window"])
        buf["window"], buf["capacity"] = new, cap

    def plan(self, device_ids, geom: tuple, bucket: int, rows=None) -> dict:
        """The windowed step's vectors for one batch of single frames,
        [bucket] each: ``idx`` the slot of each row (``capacity`` for a
        padded row: its write is dropped, its read clipped) and ``pos``
        where its frame is written; ``emit``, the indices into
        ``device_ids`` whose window is full once this frame is in. Advances
        the host's bookkeeping: the round is committed when the step is
        (``lost`` when it raised)."""
        geom = tuple(geom)
        buf = self._buf(geom)
        for did in device_ids:
            st = self._streams.get(did)
            if st is not None and st[0] != geom:
                self.pop(did)
                if st[3]:
                    self._note("geometry", 1)
                st = None
            if st is None:
                if buf["free"]:
                    slot = buf["free"].pop()
                else:
                    slot, buf["used"] = buf["used"], buf["used"] + 1
                self._streams[did] = [geom, slot, 0, 0]
        self.ensure(geom, max(buf["used"], 1))
        idx = np.full(bucket, buf["capacity"], np.int32)
        pos = np.zeros(bucket, np.int32)
        emit: List[int] = []
        for i, did in enumerate(device_ids):
            st = self._streams[did]
            r = i if rows is None else rows[i]
            idx[r], pos[r] = st[1], st[2]
            st[2] = (st[2] + 1) % self.clip_len
            st[3] = min(st[3] + 1, self.clip_len)
            if st[3] == self.clip_len:
                emit.append(i)
        return {"idx": idx, "pos": pos, "emit": emit}

    def restart(self, device_ids, reason: str) -> int:
        """These streams' windows start anew (a frame that was read did
        not reach them); counted for those that held a frame."""
        n = 0
        for did in device_ids:
            st = self._streams.get(did)
            if st is not None and st[3]:
                st[2] = st[3] = 0
                n += 1
        if n:
            self._note(reason, n)
        return n

    def lost(self, geom: tuple, reason: str) -> None:
        """The geometry's buffer went into a step that raised: it is gone
        (donated). Every stream in it starts anew in a new buffer."""
        geom = tuple(geom)
        buf = self._bufs.get(geom)
        if buf is not None:
            buf["window"] = None
        self.restart([d for d, st in self._streams.items()
                      if st[0] == geom], reason)

    def held(self, device_id: str) -> int:
        """Frames the stream's window holds (0 for an unknown stream)."""
        st = self._streams.get(device_id)
        return st[3] if st is not None else 0

    def nbytes(self) -> int:
        """Device bytes the pool holds (obs/hbm.py ``register_pool``)."""
        # list(): the memory ledger reads while the tick thread may be
        # adding a geometry
        return int(sum(b["window"].nbytes for b in list(self._bufs.values())
                       if b["window"] is not None))
