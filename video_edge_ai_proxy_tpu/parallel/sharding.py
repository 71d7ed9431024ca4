"""Logical-axis → mesh-axis sharding rules.

Model code names its weight axes logically (`models/transformer.py` uses
"embed"/"qkv"/"mlp" via `nn.with_logical_partitioning`); this module owns
the single mapping from those names onto mesh axes, so changing the
parallelism layout never touches a model file — the scaling-book recipe:
pick a mesh, annotate shardings, let XLA insert the collectives.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default rules: tensor-parallel over head/mlp width, fsdp over embed,
# experts over ep. Entries absent -> replicated.
DEFAULT_RULES = (
    ("embed", "fsdp"),
    ("qkv", "tp"),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("expert", "ep"),
    ("batch", "dp"),
    ("seq", "sp"),
)


def param_shardings(mesh: Mesh, params: Any, rules=DEFAULT_RULES):
    """Tree of NamedShardings for a (possibly nn.Partitioned-boxed) param
    tree. Unannotated leaves are fully replicated."""
    specs = nn.get_partition_spec(params)
    return nn.logical_to_mesh_sharding(specs, mesh, rules)


def batch_sharding(mesh: Mesh, ndim: int, batch_axes=("dp",)) -> NamedSharding:
    """Shard the leading (batch) dim over ``batch_axes``, replicate the rest."""
    return NamedSharding(mesh, P(batch_axes, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_put(frames: Any, sharding: NamedSharding):
    """Sharded H2D with one async ``device_put`` per mesh slice.

    ``jax.device_put(host_array, NamedSharding)`` routes through a single
    synchronous transfer path on several backends; issuing one per-slice
    ``device_put`` lets every chip's DMA engine pull its own slice
    concurrently, and ``make_array_from_single_device_arrays`` stitches
    the committed pieces back into one global array with the requested
    sharding (no data movement). Slices of a C-contiguous host array
    along the leading (batch) axis are themselves contiguous views, so
    each transfer is a single flat copy. A batch the mesh cannot divide
    raises here, before anything is placed."""
    dmap = sharding.addressable_devices_indices_map(frames.shape)
    arrs = [jax.device_put(frames[idx], d) for d, idx in dmap.items()]
    return jax.make_array_from_single_device_arrays(
        frames.shape, sharding, arrs)


def assemble_sharded(pieces: Any, shape: tuple, sharding: NamedSharding):
    """Stitch per-shard single-device arrays into one global dp-sharded
    array with NO data movement on the common dp-only mesh.

    ``pieces[s]`` is shard s's batch segment (``shape[0]/len(pieces)``
    rows) already committed on that shard's primary device — e.g. a
    per-shard state-pool gather. When an extra mesh axis replicates the
    batch block over several devices, the piece is device_put to the
    replicas (device-to-device)."""
    seg = shape[0] // max(1, len(pieces))
    arrs = []
    for d, idx in sharding.addressable_devices_indices_map(shape).items():
        s = (idx[0].start or 0) // seg if seg else 0
        piece = pieces[s]
        if d not in piece.devices():
            piece = jax.device_put(piece, d)
        arrs.append(piece)
    return jax.make_array_from_single_device_arrays(shape, sharding, arrs)


def unbox(params: Any) -> Any:
    """Strip nn.Partitioned boxes (for code that wants raw arrays)."""
    return nn.meta.unbox(params)


def place_params(mesh: Mesh, params: Any, rules=DEFAULT_RULES):
    """Unbox a Partitioned param tree and device-put it onto the mesh per
    the rules (host -> sharded device buffers)."""
    shardings = param_shardings(mesh, params, rules)
    return jax.device_put(unbox(params), shardings)
