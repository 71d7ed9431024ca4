"""Device-resident per-track clip ring for the temporal cascade.

Modeled on the r12 quality thumbnail pool (engine/stream_state.py
``_ThumbPool``), re-keyed from stream to track: one static-shape device
array ``[slots, clip_len, side, side, 3] uint8`` holds every live
track's last ``clip_len`` crop tiles as a ring. Slot assignment is the
pools' shared host-side ``SlotMap`` (track key -> row, free list); per-row
write cursors and fill counts also live on the host, so the ONLY host<->device
traffic is the new tiles themselves plus two small int32 index vectors
per scatter (``vep_h2d_*`` aux bytes) — the clip contents NEVER round-
trip to the host between ticks (ISSUE 14 acceptance: no per-tick D2H of
the state pool; the head consumes clips via a device-side gather).

Row 0 is permanently zero and is the gather target for padded bucket
slots, so a padded head batch reads all-zero clips instead of stale
track state. Capacity grows in ``_GROW``-row increments via ``jnp.pad``
(device-to-device copy); scatter/gather batch sizes are bucketed by the
caller, so program shapes stay bounded. Slot reuse needs no device-side
zeroing: ``gather`` only ever returns rows whose fill count reached
``clip_len``, by which point the new occupant overwrote every time
position.

Lazy jax imports (CLAUDE.md): constructing the pool is backend-free;
the device array materializes on first ``scatter``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..engine.stream_state import SlotMap


class TrackStatePool(SlotMap):
    """Per-track device clip ring with host-side slot bookkeeping."""

    _GROW = 8

    __slots__ = ("side", "clip_len", "device", "_cursor", "_fill", "_pool",
                 "_capacity")

    def __init__(self, side: int, clip_len: int, device=None):
        self.side = int(side)
        self.clip_len = int(clip_len)
        # Mesh-sharded serving: each shard's sub-pool commits its ring to
        # that shard's chip, so scatter/gather traffic stays local to the
        # chip that serves the shard's streams. None = default placement
        # (single-chip behavior unchanged).
        self.device = device
        SlotMap.__init__(self, first=1)       # track key -> row (>= 1)
        self._cursor: Dict[int, int] = {}     # row -> next write position
        self._fill: Dict[int, int] = {}       # row -> frames written (<= T)
        self._pool = None                     # [cap, T, side, side, 3] u8
        self._capacity = 0

    def pop(self, key: str, default=None):
        """Release a track's slot back to the free list."""
        row = SlotMap.pop(self, key)
        if row is None:
            return default
        self._cursor.pop(row, None)
        self._fill.pop(row, None)
        return row

    # -- occupancy ---------------------------------------------------------

    @property
    def high_water(self) -> int:
        """Highest row ever assigned (slot-conservation evidence: stays
        bounded across track churn because freed rows are reused)."""
        return self.high - 1

    def slots_in_use(self) -> int:
        return len(self._slots)

    @property
    def array(self):
        """The live device array (None before the first scatter). Exposed
        for the no-D2H invariant test, never for host fetches."""
        return self._pool

    def full(self, key: str) -> bool:
        """True once the track has a complete ``clip_len``-frame clip."""
        row = self._slots.get(key)
        return row is not None and self._fill.get(row, 0) >= self.clip_len

    def nbytes(self) -> int:
        """Device bytes held by the ring RIGHT NOW (0 before the array
        materializes) — the obs/hbm.py ``register_pool`` protocol.
        Capacity-based, not occupancy-based: grow-by-8 rows stay
        allocated after their tracks churn out, and the HBM plane
        accounts for what the allocator holds, not what is logically
        live. Metadata read only (``.nbytes``) — no transfer, no sync."""
        return int(self._pool.nbytes) if self._pool is not None else 0

    # -- device ring -------------------------------------------------------

    def _ensure(self, rows: int) -> None:
        import jax.numpy as jnp

        need = rows + 1
        if self._pool is None:
            cap = ((max(need, 2) + self._GROW - 1)
                   // self._GROW) * self._GROW
            self._pool = jnp.zeros(
                (cap, self.clip_len, self.side, self.side, 3), jnp.uint8)
            if self.device is not None:
                import jax

                # Committed arrays stay put: every later .at[].set / pad
                # keeps the ring on this shard's chip.
                self._pool = jax.device_put(self._pool, self.device)
            self._capacity = cap
        elif need > self._capacity:
            grow = ((need - self._capacity + self._GROW - 1)
                    // self._GROW) * self._GROW
            self._pool = jnp.pad(
                self._pool, ((0, grow), (0, 0), (0, 0), (0, 0), (0, 0)))
            self._capacity += grow

    def _row_for(self, key: str) -> int:
        row, new = self.take(key)
        if new:
            self._cursor[row] = 0
            self._fill[row] = 0
        return row

    def scatter(self, keys: Sequence[str], tiles: np.ndarray,
                bucket: Optional[int] = None) -> int:
        """Append one new crop tile per track to its ring.

        ``tiles`` is ``uint8 [n, side, side, 3]`` host frames (one per
        key, keys unique). With ``bucket`` the index vectors and tile
        batch are padded to that length by REPEATING the last entry —
        a duplicate write of identical data to the same cell, harmless
        and shape-stable (bounded program count). Returns the aux index
        bytes shipped (the two int32 vectors); the caller adds the tile
        bytes for ``vep_h2d_*`` accounting.
        """
        import jax.numpy as jnp

        rows = [self._row_for(k) for k in keys]
        self._ensure(max(rows))
        pos = [self._cursor[r] for r in rows]
        if bucket is not None and bucket > len(rows):
            pad = bucket - len(rows)
            rows_v = rows + [rows[-1]] * pad
            pos_v = pos + [pos[-1]] * pad
            tiles = np.concatenate(
                [tiles, np.repeat(tiles[-1:], pad, axis=0)], axis=0)
        else:
            rows_v, pos_v = rows, pos
        rows_np = np.asarray(rows_v, np.int32)
        pos_np = np.asarray(pos_v, np.int32)
        self._pool = self._pool.at[rows_np, pos_np].set(jnp.asarray(tiles))
        for r in rows:
            self._cursor[r] = (self._cursor[r] + 1) % self.clip_len
            self._fill[r] = min(self._fill[r] + 1, self.clip_len)
        return int(rows_np.nbytes + pos_np.nbytes)

    def gather_indices(self, keys: Sequence[str],
                       bucket: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side index plan for a time-ordered device gather.

        Returns ``(slot_idx [bucket], time_idx [bucket, T])`` int32.
        ``time_idx[i]`` unrolls track i's ring oldest-first (the cursor
        points at the next overwrite target, which for a full ring is
        the oldest frame). Padded slots index permanent-zero row 0.
        """
        T = self.clip_len
        slot_idx = np.zeros((bucket,), np.int32)
        time_idx = np.zeros((bucket, T), np.int32)
        base = np.arange(T, dtype=np.int32)
        for i, key in enumerate(keys[:bucket]):
            row = self._slots.get(key)
            if row is None:
                continue
            slot_idx[i] = row
            time_idx[i] = (self._cursor.get(row, 0) + base) % T
        return slot_idx, time_idx

    def gather(self, slot_idx: np.ndarray, time_idx: np.ndarray):
        """Time-ordered clips ``[bucket, T, side, side, 3] uint8`` as a
        DEVICE array (eager jnp take/take_along_axis, same pattern as the
        r12 quality gather): the pool contents never touch the host."""
        import jax.numpy as jnp

        clips = jnp.take(self._pool, jnp.asarray(slot_idx), axis=0)
        t = jnp.asarray(time_idx)[:, :, None, None, None]
        return jnp.take_along_axis(clips, t, axis=1)


def shard_devices(mesh, shards: int) -> list:
    """Primary device per dp index: shard s's pools commit here. With
    extra mesh axes the dp block spans several devices; the first is the
    primary (assemble_sharded replicates to the rest on demand)."""
    axis = list(mesh.axis_names).index("dp")
    blocks = np.moveaxis(np.asarray(mesh.devices), axis, 0)
    blocks = blocks.reshape(shards, -1)
    return [blocks[s][0] for s in range(shards)]


class ShardedTrackStatePool:
    """dp-sharded twin of TrackStatePool for mesh-native cascade serving.

    One sub-ring per mesh shard, committed to that shard's chip, so a
    track's clip state lives where its stream is served (streams are
    pinned to shards by ``engine.collector.stream_shard``). Presents the
    same dict-protocol + scatter/gather surface the scheduler and the
    engine GC already consume, plus :meth:`plan` — the shard-segmented
    head-batch layout (the scheduler maps head outputs back through the
    returned rows). ``gather`` stitches the per-shard sub-gathers into
    one dp-sharded device batch (``parallel.sharding.assemble_sharded``)
    so the cascade head program reads every chip's clips locally — the
    state pool never migrates clips between chips and never round-trips
    them through the host.
    """

    def __init__(self, side: int, clip_len: int, *, mesh, shards: int,
                 shard_of, buckets: Sequence[int] = (4, 8, 16, 32, 64)):
        self.side = int(side)
        self.clip_len = int(clip_len)
        self.mesh = mesh
        self.shards = max(1, int(shards))
        self._shard_of = shard_of            # track key -> shard index
        self._buckets = tuple(
            sorted(b for b in buckets if b % self.shards == 0)
        ) or (self.shards,)
        self.pools = [TrackStatePool(side, clip_len, device=d)
                      for d in shard_devices(mesh, self.shards)]

    # -- dict-protocol surface (same as TrackStatePool) --------------------

    def _pool_for(self, key: str) -> TrackStatePool:
        return self.pools[self._shard_of(key)]

    def __len__(self) -> int:
        return sum(len(p) for p in self.pools)

    def __iter__(self):
        for p in self.pools:
            yield from p

    def __contains__(self, key: str) -> bool:
        return key in self._pool_for(key)

    def pop(self, key: str, default=None):
        return self._pool_for(key).pop(key, default)

    @property
    def high_water(self) -> int:
        return max(p.high_water for p in self.pools)

    def slots_in_use(self) -> int:
        return sum(p.slots_in_use() for p in self.pools)

    @property
    def array(self):
        """Per-shard device arrays (None before first scatter)."""
        return [p.array for p in self.pools]

    def full(self, key: str) -> bool:
        return self._pool_for(key).full(key)

    def nbytes(self) -> Dict[str, int]:
        """Per-shard ring bytes ``{shard: bytes}`` — the obs/hbm.py
        sharded ``register_pool`` shape (the tracker sums shards for the
        aggregate; the exactness pin checks each shard against its
        sub-ring's ``.nbytes``)."""
        return {str(s): p.nbytes() for s, p in enumerate(self.pools)}

    # -- sharded scatter / gather ------------------------------------------

    def scatter(self, keys: Sequence[str], tiles: np.ndarray,
                bucket: Optional[int] = None) -> int:
        """Route each track's tile to its shard's sub-ring. ``bucket``
        (the caller's aggregate pad target) is recomputed PER SHARD from
        the bucket ladder — each chip's scatter program stays
        shape-stable independently."""
        per: List[list] = [[] for _ in range(self.shards)]
        for i, key in enumerate(keys):
            per[self._shard_of(key)].append((i, key))
        cap = self._buckets[-1] // self.shards
        aux = 0
        for s, entries in enumerate(per):
            if not entries:
                continue
            entries = entries[:cap]
            sub_keys = [k for _, k in entries]
            sub_tiles = tiles[[i for i, _ in entries]]
            sub_bucket = next(
                (b for b in self._buckets
                 if b // self.shards >= len(entries)), None)
            aux += self.pools[s].scatter(
                sub_keys, sub_tiles,
                bucket=(sub_bucket // self.shards) if sub_bucket else None)
        return aux

    def plan(self, keys: Sequence[str]):
        """Shard-segmented head-batch layout for ``keys`` (due tracks):
        ``(slot_idx [B], time_idx [B, T], rows, B)``. ``rows[i]`` is the
        global batch row of ``keys[i]`` (-1 = dropped: that shard's
        segment overflowed the largest bucket; the track stays due and
        rides the next cadence). Padded rows gather each sub-ring's
        permanent-zero row 0."""
        S = self.shards
        per: List[list] = [[] for _ in range(S)]
        rows = [-1] * len(keys)
        cap = self._buckets[-1] // S
        for i, key in enumerate(keys):
            s = self._shard_of(key)
            if len(per[s]) < cap:
                per[s].append((i, key))
        need = max((len(p) for p in per), default=0) or 1
        bucket = next(b for b in self._buckets if b // S >= need)
        seg = bucket // S
        T = self.clip_len
        slot_idx = np.zeros((bucket,), np.int32)
        time_idx = np.zeros((bucket, T), np.int32)
        for s, entries in enumerate(per):
            if not entries:
                continue
            sub_slot, sub_time = self.pools[s].gather_indices(
                [k for _, k in entries], seg)
            slot_idx[s * seg:(s + 1) * seg] = sub_slot
            time_idx[s * seg:(s + 1) * seg] = sub_time
            for j, (i, _key) in enumerate(entries):
                rows[i] = s * seg + j
        return slot_idx, time_idx, rows, bucket

    def gather(self, slot_idx: np.ndarray, time_idx: np.ndarray):
        """dp-sharded clips ``[B, T, side, side, 3] uint8``: per-shard
        local gathers stitched with no cross-chip movement."""
        from ..parallel.sharding import assemble_sharded, batch_sharding

        bucket = int(slot_idx.shape[0])
        seg = bucket // self.shards
        pieces = []
        for s, pool in enumerate(self.pools):
            if pool.array is None:
                pool._ensure(0)   # committed zero ring (idle shard)
            pieces.append(pool.gather(
                slot_idx[s * seg:(s + 1) * seg],
                time_idx[s * seg:(s + 1) * seg]))
        shape = (bucket, self.clip_len, self.side, self.side, 3)
        return assemble_sharded(pieces, shape, batch_sharding(self.mesh, 5))
