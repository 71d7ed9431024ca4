"""What a serving step carries from round to round, per stream, on the device.

The reference keeps no model state at all (it ships frames to external
clients, `/root/reference/README.md:5-27`). Here a step may take a
per-stream device buffer in and give it back, a stream owning a slot of it:
the quality thumbnails (:class:`_ThumbPool`), a streaming head's state
(:class:`StreamStatePool`) and the clip windows (:class:`ClipWindowPool`);
the cascade's track tiles (``temporal/state_pool.py``) are held the same
way beside the step. Two decisions live here once. :class:`SlotMap` is the
``device_id -> slot`` allocator under all four, and the dict-like surface
the tick loop's GC reads (each pool keeps its own growth rule: it decides
compiled shapes). And the three a step carries answer
``InferenceEngine._dispatch`` through one surface: ``carry`` (the batch's
:class:`Carry`), ``step_args`` / ``donated`` (the layout ``_step`` reckons
``donate_argnums`` from) and ``nbytes()`` under ``ledger``, the pool's
name in the HBM ledger (obs/hbm.py).

A streaming head (``models/lfm2.py``, ``models/xing4.py``) carries, for
every camera, whatever kinds of state its ``empty_state(slots)`` declares:
a dict of named buffers and, by the same names, the axis of each that
counts the slots (LFM2: ``conv`` [slots, conv layers, L-1, d] and ``kv`` =
(keys, values), each [attention layers, slots, kv heads, head_dim,
max_context]; Xing4: ``latent`` [blocks, slots, max_context, 576], one row
of 576 numbers a position a block, and ``exit`` [slots, d]).

One :class:`StreamStatePool` a stream-head model: the model's kinds and
``tokens`` [slots, rounds a context x D] (the ids decoded since the
stream's reset), indexed by slot. The serving
step reads and writes by slot index INSIDE the program and the buffers are
donated, so the state never crosses to the host and the caches are
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

All methods run on the tick thread (single writer).
"""

from __future__ import annotations

import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np


class SlotMap:
    """``key -> slot`` with a free list and a high-water mark: the one
    allocator under the per-stream pools, which are slot maps with device
    buffers (the clip windows hold one a geometry). A new key takes the
    slot freed last (LIFO), else the next never given, counting up from
    ``first`` (the thumbnail and track pools reserve row 0 as their zero
    row). Dict-like as the tick loop's GC reads its containers (its truth
    is its length); iteration is over a copy, so the caller may ``pop``
    while it walks."""

    __slots__ = ("_slots", "_free", "high")

    def __init__(self, first: int = 0):
        self._slots: Dict[str, int] = {}
        self._free: List[int] = []
        self.high = int(first)      # one past the highest slot ever given

    def __iter__(self):
        return iter(list(self._slots))

    def __len__(self) -> int:
        return len(self._slots)

    def __contains__(self, key) -> bool:
        return key in self._slots

    def take(self, key):
        """``(slot, is_new)``: the key's slot, given now if it had none."""
        slot = self._slots.get(key)
        if slot is not None:
            return slot, False
        if self._free:
            slot = self._free.pop()
        else:
            slot, self.high = self.high, self.high + 1
        self._slots[key] = slot
        return slot, True

    def pop(self, key, default=None):
        """Free the key's slot; the slot, or ``default`` for a stranger."""
        slot = self._slots.pop(key, None)
        if slot is None:
            return default
        self._free.append(slot)
        return slot


class Carry:
    """What one carried state adds to one step call: made by the state's
    ``carry(device_ids, bucket, rows, geom)`` for a batch, used up by
    ``InferenceEngine._dispatch``. ``args()``: its arguments after
    ``frames``, the buffer first, read when the step is called;
    ``commit(outputs)`` pops its key and takes the buffer back; ``lost()``
    after a step that raised with the buffer donated; ``wait`` where the
    state serialises its steps; ``trace``: its batch-trace fields;
    ``emit``: the indices into ``device_ids`` still owed a result (None:
    all); ``step``: the program to run in the model step's place;
    ``aux_nbytes``: what its index vectors add to the placement's bytes."""

    __slots__ = ("args", "commit", "lost", "wait", "trace", "emit", "step",
                 "aux_nbytes")

    def __init__(self, args, commit, lost=None, wait=None, trace=None,
                 emit=None, step=None, aux_nbytes: int = 0):
        self.args, self.commit = args, commit
        self.lost = lost or (lambda: None)
        self.wait, self.trace = wait, trace or {}
        self.emit, self.step, self.aux_nbytes = emit, step, aux_nbytes


class _Thumbs:
    """The thumbnail pools as a state a step carries: the previous tick's
    rows in as a gather view of the pool (never donated), this tick's back
    under ``quality_thumbs`` and scattered for the next diff."""

    __slots__ = ()
    ledger = "thumbs"
    key = "quality_thumbs"
    step_args = 1
    donated = ()

    def carry(self, device_ids, bucket: int, rows=None, geom=None) -> Carry:
        idx = self.gather_indices(device_ids, bucket, rows=rows)
        return Carry(
            # a device-side gather from the resident pool (no host rows
            # cross); the pop keeps the new rows out of _emit's D2H fetch
            args=lambda: (self.gather(idx),),
            commit=lambda outputs: self.scatter(
                device_ids, outputs.pop(self.key), rows=rows),
            aux_nbytes=(sum(int(a.nbytes) for a in idx)
                        if isinstance(idx, list) else int(idx.nbytes)))


class _ThumbPool(_Thumbs, SlotMap):
    """Device-resident per-stream quality-thumbnail state: one
    [capacity, th, tw] f32 device array plus a host slot map. The
    previous tick's thumbnails for a batch are a device-side ``jnp.take``
    keyed by slot indices; this tick's rows scatter back with
    ``.at[idx].set`` — thumbnail state never crosses back to host, and the
    dispatch loop ships only a [bucket] int32 index vector.

    Row 0 is a permanent zero row: first-seen streams (and padded batch
    slots) gather it, preserving the zero-reference/first-diff contract
    ``frame_quality_stats`` documents. A ``SlotMap`` (stream -> pool row),
    so the tick loop's debounced per-stream GC treats it exactly like the
    tracker/annotation state dicts; a freed row's stale contents are
    unreachable: nothing gathers a row until scatter() reassigns it, which
    overwrites it first. All methods run on the tick thread.
    """

    __slots__ = ("side", "device", "_pool", "_capacity")

    _GROW = 64    # rows added per capacity growth (keeps re-pads rare)

    def __init__(self, side: int, device=None):
        self.side = int(side)
        # a sharded parent pins each sub-pool to its mesh slice's lead
        # device, so gathers/scatters stay chip-local; None: the default
        self.device = device
        SlotMap.__init__(self, first=1)    # device_id -> pool row (>= 1)
        self._pool = None                  # lazy: jax import stays off the
        self._capacity = 0                 # control plane (CLAUDE.md)

    def _ensure(self, rows: int) -> None:
        import jax.numpy as jnp

        if self._pool is None:
            cap = max(self._GROW, rows)
            pool = jnp.zeros((cap, self.side, self.side), jnp.float32)
            if self.device is not None:
                import jax

                pool = jax.device_put(pool, self.device)
            self._pool = pool
            self._capacity = cap
        elif rows > self._capacity:
            grow = -(-(rows - self._capacity) // self._GROW) * self._GROW
            # Padding a committed array computes on (and stays on) its
            # device, so the shard pinning survives growth.
            self._pool = jnp.pad(self._pool, ((0, grow), (0, 0), (0, 0)))
            self._capacity += grow

    def gather_indices(self, device_ids, bucket: int, rows=None) -> np.ndarray:
        """[bucket] int32 gather rows for a batch, slot order: each
        known stream's row, row 0 (zeros) for first-seen streams and
        padded slots. ``rows`` (shard-segmented layouts) maps slot i to
        its batch row; None keeps the legacy identity order. This
        vector is the only host->device bytes the quality path still
        ships per batch."""
        idx = np.zeros(bucket, np.int32)
        for i, did in enumerate(device_ids):
            r = i if rows is None else rows[i]
            idx[r] = self._slots.get(did, 0)
        return idx

    def gather(self, idx: np.ndarray):
        """Previous-tick [bucket, th, tw] rows as a device-side gather."""
        import jax.numpy as jnp

        self._ensure(1)
        return jnp.take(self._pool, jnp.asarray(idx), axis=0)

    def scatter(self, device_ids, thumbs, rows=None) -> None:
        """Store this tick's [>=n, th, tw] device rows (the step output,
        still async) for next tick's diff; assigns pool rows on first
        sight. ``rows`` names each stream's source row inside ``thumbs``
        (shard-segmented layouts); None = slot order, legacy path."""
        import jax.numpy as jnp

        pool_rows = [self.take(did)[0] for did in device_ids]
        if not pool_rows:
            return
        self._ensure(max(pool_rows) + 1)
        idx = jnp.asarray(np.asarray(pool_rows, np.int32))
        if rows is None:
            src = thumbs[:len(pool_rows)]
        else:
            src = jnp.take(
                thumbs, jnp.asarray(np.asarray(rows, np.int32)), axis=0)
        self._pool = self._pool.at[idx].set(src)

    def nbytes(self) -> int:
        """Device bytes held by the thumbnail ring right now (0 before
        first scatter) — obs/hbm.py ``register_pool`` tap. Capacity-
        based like the track-state ring: grown rows stay allocated after
        their streams GC. Metadata only, no transfer."""
        return int(self._pool.nbytes) if self._pool is not None else 0


class _ShardedThumbPool(_Thumbs):
    """Per-mesh-slice thumbnail state for mesh serving (r17 tentpole
    leg 3): one ``_ThumbPool`` per dp shard, each pinned to its slice's
    lead device, speaking the collector's shard-segmented row layout
    (``group.rows``). ``gather`` assembles the per-shard device takes
    into one dp-sharded [bucket, th, tw] array (the same sharding the
    frames carry, so the compiled step sees one stable signature);
    ``scatter`` splits the step's sharded thumbnail output back per
    slice via its addressable shards — a stream's t-1 thumbnail lives
    on the chip that serves its frames, and no thumbnail bytes ever
    cross the host or a chip boundary. Dict-like as ``_ThumbPool``."""

    __slots__ = ("side", "shards", "_mesh", "_shard_of", "_subs")

    def __init__(self, side: int, *, mesh, shards: int, shard_of):
        from ..temporal.state_pool import shard_devices

        self.side = int(side)
        self.shards = int(shards)
        self._mesh = mesh
        self._shard_of = shard_of
        self._subs = [
            _ThumbPool(side, device=d)
            for d in shard_devices(mesh, self.shards)
        ]

    def __iter__(self):
        ids: List[str] = []
        for sub in self._subs:
            ids.extend(sub)
        return iter(ids)

    def __len__(self) -> int:
        return sum(len(sub) for sub in self._subs)

    def pop(self, device_id: str, default=None):
        self._subs[self._shard_of(device_id) % self.shards].pop(device_id)
        return default

    def gather_indices(self, device_ids, bucket: int, rows=None):
        """Per-shard [seg] int32 local gather rows (list, one array per
        shard). Row r of the batch lives in shard r // seg at local row
        r % seg — the collector's segmented layout."""
        seg = max(1, bucket // self.shards)
        per = [np.zeros(seg, np.int32) for _ in range(self.shards)]
        for i, did in enumerate(device_ids):
            r = i if rows is None else rows[i]
            per[r // seg][r % seg] = self._subs[r // seg]._slots.get(did, 0)
        return per

    def gather(self, idx):
        """Previous-tick [bucket, th, tw] thumbnails as one dp-sharded
        array: a chip-local take per shard, assembled without any
        cross-chip movement."""
        import jax.numpy as jnp

        from ..parallel import assemble_sharded, batch_sharding

        pieces = []
        for s, sub in enumerate(self._subs):
            sub._ensure(1)
            pieces.append(jnp.take(sub._pool, jnp.asarray(idx[s]), axis=0))
        bucket = sum(int(p.shape[0]) for p in pieces)
        return assemble_sharded(
            pieces, (bucket, self.side, self.side),
            batch_sharding(self._mesh, 3),
        )

    def scatter(self, device_ids, thumbs, rows=None) -> None:
        """Route this tick's sharded [bucket, th, tw] step output into
        the per-shard pools: each shard scatters from its own
        addressable slice (chip-local), with a sliced-view fallback
        when the compiled output's layout hides a shard."""
        bucket = int(thumbs.shape[0])
        seg = max(1, bucket // self.shards)
        by_shard: Dict[int, List[tuple]] = {}
        for i, did in enumerate(device_ids):
            r = i if rows is None else rows[i]
            by_shard.setdefault(r // seg, []).append((r % seg, did))
        pieces: Dict[int, Any] = {}
        for sh in getattr(thumbs, "addressable_shards", ()):
            if int(sh.data.shape[0]) != seg:
                continue   # unexpected output layout: fallback below
            start = sh.index[0].start or 0
            pieces.setdefault(start // seg, sh.data)
        for s, pairs in sorted(by_shard.items()):
            piece = pieces.get(s)
            if piece is None:
                piece = thumbs[s * seg:(s + 1) * seg]
            self._subs[s].scatter(
                [did for _, did in pairs], piece,
                rows=[r for r, _ in pairs],
            )

    def nbytes(self) -> Dict[str, int]:
        """Per-shard thumbnail ring bytes ``{shard: bytes}`` — the
        obs/hbm.py sharded ``register_pool`` shape (each sub-pool's
        figure is exact against its own ring's ``.nbytes``)."""
        return {str(s): sub.nbytes() for s, sub in enumerate(self._subs)}


def first_context_rounds(device_id: str, mod: int) -> int:
    """Rounds of a stream's first context (de-phased resets)."""
    return 1 + zlib.crc32(device_id.encode()) % max(1, int(mod))


class StreamStatePool(SlotMap):
    __slots__ = ("model", "cfg", "state", "capacity", "_grow", "_ctx",
                 "_note")

    kinds = ("stream",)     # the step kinds that carry it (ModelSpec.kind)
    ledger = "stream_state"
    key = "state"           # the buffers come back as the output's "state",
    step_args = 5           # same shapes: (state, idx, pos0, reset, rounds)
    donated = (0,)          # with the state rewritten in place

    def __init__(self, model, grow: int = 64,
                 note_round: Optional[Callable[[int, int, int], None]] = None):
        self.model = model                 # answers ``empty_state(slots)``
        self.cfg = model.cfg               # the round's sizes and policy
        self.state: Optional[dict] = None  # lazy: jax stays off the
        self.capacity = 0                  # control plane (CLAUDE.md)
        self._grow = max(1, int(grow))
        # (prefill tokens, decode tokens, resets) of a committed round
        self._note = note_round or (lambda prefill, decode, resets: None)
        SlotMap.__init__(self)
        # device_id -> [positions committed, rounds since the reset,
        # rounds left of context one]
        self._ctx: Dict[str, list] = {}

    def pop(self, device_id: str, default=None):
        """Forget a stream: its slot returns to the free list. Nothing
        reads a freed slot's rows before its next owner's first round,
        which is a reset."""
        SlotMap.pop(self, device_id)
        self._ctx.pop(device_id, None)
        return default

    def ensure(self, slots: int) -> None:
        """Room for ``slots`` streams, grown in steps of ``grow`` (a growth
        changes the step's shapes: its programs compile again)."""
        if slots <= self.capacity and self.state is not None:
            return
        import jax
        import jax.numpy as jnp

        cap = -(-max(slots, 1) // self._grow) * self._grow
        c = self.cfg
        state, axes = self.model.empty_state(cap)
        state["tokens"] = jnp.full(
            (cap, c.max_rounds * c.decode_steps), -1, jnp.int32)
        if self.state is not None:
            # what the streams hold moves into the first slots of the new
            # buffers, each kind along its own slot axis
            for kind, new in state.items():
                axis = axes.get(kind, 0)
                state[kind] = jax.tree_util.tree_map(
                    lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                        a, b, 0, axis), new, self.state[kind])
        self.state = state
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
            if self.take(did)[1]:
                self._ctx[did] = [
                    0, 0, first_context_rounds(did, c.max_rounds)]
        self.ensure(max(1, self.high))
        idx = np.full(bucket, self.capacity, np.int32)
        pos0 = np.full(bucket, n_i, np.int32)
        reset = np.ones(bucket, bool)
        rounds = np.zeros(bucket, np.int32)
        for i, did in enumerate(device_ids):
            r = i if rows is None else rows[i]
            ctx = self._ctx[did]
            length, _, first_left = ctx
            fresh = (length == 0
                     or length + c.round_positions > c.head.max_context
                     or first_left == 0)
            if fresh:
                length, ctx[1] = n_i, 0
                if first_left == 0:
                    ctx[2] = -1                      # context one is over
            if first_left > 0:
                ctx[2] -= 1
            idx[r], pos0[r], reset[r] = self._slots[did], length, fresh
            rounds[r] = ctx[1]
            ctx[0] = length + c.round_positions
            ctx[1] += 1
        return {"idx": idx, "pos0": pos0, "reset": reset, "rounds": rounds}

    def carry(self, device_ids, bucket: int, rows=None, geom=None) -> Carry:
        """Slots, reset and index vectors: the only per-stream host work
        a stream head adds to the tick thread, timed as ``pool_s``."""
        pc0 = time.perf_counter()
        plan = self.plan(device_ids, bucket, rows=rows)
        real = plan["idx"] < self.capacity
        n, c = int(real.sum()), self.cfg
        trace = {
            "head_prefill_tokens": n * c.visual_tokens,
            "head_decode_steps": c.decode_steps,
            "head_ctx_mean": float(plan["pos0"][real].mean()
                                   + c.round_positions) if n else 0.0,
            "head_resets": int(plan["reset"][real].sum())}
        trace["pool_s"] = time.perf_counter() - pc0

        def commit(outputs):
            self.state = outputs.pop(self.key)
            self._note(trace["head_prefill_tokens"], n * c.decode_steps,
                       trace["head_resets"])

        return Carry(
            args=lambda: (self.state, plan["idx"], plan["pos0"],
                          plan["reset"], plan["rounds"]),
            commit=commit, lost=self.lost, wait=self.wait, trace=trace)

    def lost(self) -> None:
        """The buffers went into a step that raised (donated) and the
        host's bookkeeping ran ahead of the device: the pool starts empty,
        every stream anew in a new slot of new buffers."""
        self.state, self.capacity = None, 0
        SlotMap.__init__(self)
        self._ctx.clear()

    def wait(self) -> None:
        """Block until the step that last wrote the state has finished.
        One stream step on the device at a time: the next takes this one's
        state anyway, and two launched together hold their temporaries
        (GBs) together. Where the device sets the pace this wait is most
        of a round; it is no part of the step call."""
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

    __slots__ = ("clip_len", "_buckets", "_note", "_program", "_bufs",
                 "_streams")

    ledger = "clip_windows"
    key = "window"          # the buffer comes back as the output's "window",
    step_args = 3           # same shape and dtype: (window, idx, pos), the
    donated = (0,)          # step's own arguments follow idx and pos

    def __init__(self, clip_len: int, buckets: Sequence[int],
                 note_restart: Optional[Callable[[str, int], None]] = None,
                 program: Optional[Callable[..., Any]] = None):
        self.clip_len = int(clip_len)
        self._buckets = tuple(sorted(buckets)) or (1,)
        self._note = note_restart or (lambda reason, n: None)
        # (geometry, bucket, slots, write_only) -> the compiled program a
        # batch runs, called as the model's step is (``carry``)
        self._program = program
        # geometry (H, W, C) -> {"window": jax.Array or None, "capacity",
        # "slots": SlotMap of this geometry's streams}
        self._bufs: Dict[tuple, dict] = {}
        # device_id -> [geometry, slot, write position, frames held]
        self._streams: Dict[str, list] = {}

    # dict-like surface for the tick loop's per-stream GC
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
            self._bufs[st[0]]["slots"].pop(device_id)
        return default

    def _buf(self, geom: tuple) -> dict:
        return self._bufs.setdefault(
            geom, {"window": None, "capacity": 0, "slots": SlotMap()})

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
                self._streams[did] = [geom, buf["slots"].take(did)[0], 0, 0]
        self.ensure(geom, max(buf["slots"].high, 1))
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

    def carry(self, device_ids, bucket: int, rows=None, geom=None) -> Carry:
        """A batch of single frames. Rows whose window is still filling
        are written and computed and only full windows are emitted (and
        advance a head); while no window of the batch is full the program
        is the write alone: nothing is computed (a stream head's round
        costs a second) and nothing comes out."""
        geom = tuple(geom)
        plan = self.plan(device_ids, geom, bucket, rows=rows)
        full = plan["emit"]
        return Carry(
            args=lambda: (self.window(geom), plan["idx"], plan["pos"]),
            commit=lambda outputs: self.put(geom, outputs.pop(self.key)),
            lost=lambda: self.lost(geom, "step_error"),
            trace={"window_rows": len(device_ids)},
            emit=full if len(full) < len(device_ids) else None,
            step=self._program and self._program(
                geom, bucket, self.capacity(geom), not full))

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
