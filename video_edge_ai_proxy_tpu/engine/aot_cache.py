"""Persistent AOT prewarm cache (r19): spawn-time cold start killer.

The XLA persistent compile cache (``EngineConfig.compile_cache_dir``,
wired in ``runner.warmup``) already makes a *restart* cheap — but a
freshly *spawned* fleet member still has to know WHICH programs to
compile before taking traffic, and ROUTER_r01 had to reset its
conservation ledger post-warmup because a member compiling in-tick
overwrites frames (latest-frame-wins) for tens of seconds. This module
adds the missing half: a versioned **prewarm manifest** JSON living
in ``aot_cache_dir`` that records the program set — one entry
per ``(model, stem, geometry, bucket)`` serving step a member has ever
compiled — so a spawned member pointed at the shared cache dir replays
the whole set at boot (every compile a cache hit) and serves its first
migrated frame within one router scrape interval (ROADMAP item 4).

Fallback contract: a manifest whose ``version`` or ``jaxlib`` stamp
does not match the running process is *ignored* (clean compile, fresh
manifest on the next record) — never an exception. The XLA cache keys
include the jaxlib/XLA fingerprint on their own; the manifest stamp
exists so we never burn boot time replaying a program list whose cache
entries are guaranteed misses.

Stdlib-only: the manifest helpers are safe to import from control-plane
code. The XLA payload itself is bound by ``utils/compile_cache.py`` —
into ``aot_cache_dir`` next to the manifest, or wherever
``JAX_COMPILATION_CACHE_DIR`` says when that is set.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional

from ..utils.logging import get_logger

log = get_logger("engine.aot_cache")

MANIFEST_VERSION = 1
MANIFEST_NAME = "prewarm_manifest.json"

# One process-wide lock: several engines in one test process may share a
# cache dir; cross-process writers are covered by the atomic rename.
_manifest_lock = threading.Lock()


def _jaxlib_stamp() -> str:
    """Version stamp binding a manifest to the compiler that filled the
    XLA cache next to it. jax import lives inside the function per the
    serving-path convention (manifest readers stay backend-free until
    someone actually asks for the stamp)."""
    try:
        import jaxlib

        return str(jaxlib.version.__version__)
    except Exception:  # pragma: no cover - jaxlib always ships jax
        return "unknown"


def manifest_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, MANIFEST_NAME)


def mesh_spec(mesh) -> List[list]:
    """Canonical manifest form of a device mesh: sorted ``[axis, size]``
    pairs for axes of size > 1; empty = single-chip. Accepts None, a
    ``jax.sharding.Mesh`` (its ``.shape`` mapping), or an already-built
    pair list — stdlib-only either way, so manifest readers stay
    backend-free."""
    if mesh is None:
        return []
    shape = getattr(mesh, "shape", None)
    if shape is not None and hasattr(shape, "items"):
        pairs = shape.items()
    else:
        pairs = mesh
    return sorted([str(a), int(n)] for a, n in pairs if int(n) > 1)


def _mesh_key(prog: Dict[str, Any]) -> tuple:
    return tuple((a, n) for a, n in mesh_spec(prog.get("mesh")))


def _program_key(prog: Dict[str, Any]) -> tuple:
    return (
        str(prog.get("model") or ""),
        str(prog.get("stem") or "classic"),
        int(prog.get("h", 0)),
        int(prog.get("w", 0)),
        int(prog.get("bucket", 0)),
        # r17 mesh-native serving: sharded and single-chip compiles of
        # the same geometry are distinct programs. Pre-r17 manifests
        # simply lack the key (= single-chip), so they stay readable.
        _mesh_key(prog),
    )


def load_manifest(cache_dir: str) -> Optional[List[Dict[str, Any]]]:
    """Read the prewarm manifest; None = nothing usable (missing,
    unparseable, or version/jaxlib mismatch — all of which mean "clean
    compile", never a crash)."""
    path = manifest_path(cache_dir)
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
    except FileNotFoundError:
        return None
    except (OSError, ValueError):
        log.warning("unreadable prewarm manifest %s; ignoring", path,
                    exc_info=True)
        return None
    if not isinstance(data, dict):
        log.warning("prewarm manifest %s is not a mapping; ignoring", path)
        return None
    if data.get("version") != MANIFEST_VERSION:
        log.warning(
            "prewarm manifest %s version %r != %d; clean compile",
            path, data.get("version"), MANIFEST_VERSION,
        )
        return None
    stamp = _jaxlib_stamp()
    if data.get("jaxlib") != stamp:
        log.warning(
            "prewarm manifest %s built under jaxlib %r, running %r; "
            "clean compile", path, data.get("jaxlib"), stamp,
        )
        return None
    programs = data.get("programs")
    if not isinstance(programs, list):
        return None
    out: List[Dict[str, Any]] = []
    seen = set()
    for prog in programs:
        if not isinstance(prog, dict):
            continue
        try:
            key = _program_key(prog)
        except (TypeError, ValueError):
            continue
        if key in seen or key[4] <= 0:
            continue
        seen.add(key)
        entry = {"model": key[0] or None, "stem": key[1],
                 "h": key[2], "w": key[3], "bucket": key[4]}
        if key[5]:
            entry["mesh"] = [[a, n] for a, n in key[5]]
        out.append(entry)
    return out


def prewarm_entries(programs: List[Dict[str, Any]],
                    mesh=None) -> List[list]:
    """Manifest programs -> ``cfg.prewarm``-shaped 5-element entries
    (``[h, w, bucket, model, stem]``; model "" = engine default).

    ``mesh`` filters to the programs recorded under that mesh spec (a
    ``jax.sharding.Mesh``, a pair list, or None = single-chip): a
    spawned mesh member replays sharded programs, a single-chip member
    replays single-chip ones, and a stale manifest from the other world
    yields no entries — clean compile, never a wrong-sharding replay."""
    want = tuple((a, n) for a, n in mesh_spec(mesh))
    return [
        [p["h"], p["w"], p["bucket"], p["model"] or "", p["stem"]]
        for p in programs
        if _mesh_key(p) == want
    ]


def record_program(
    cache_dir: str,
    *,
    model: Optional[str],
    stem: str,
    src_hw: tuple,
    bucket: int,
    mesh=None,
) -> None:
    """Merge one compiled serving-step program into the manifest
    (read-modify-write under the process lock, atomic rename so a
    concurrently spawning member never reads a torn file). A stale or
    mismatched manifest on disk is replaced, not merged into.
    ``mesh`` (Mesh / pair list / None) stamps sharded programs; the
    key is omitted entirely for single-chip so pre-r17 manifests and
    new single-chip ones stay byte-compatible."""
    prog = {
        "model": model or None,
        "stem": stem or "classic",
        "h": int(src_hw[0]),
        "w": int(src_hw[1]),
        "bucket": int(bucket),
    }
    spec = mesh_spec(mesh)
    if spec:
        prog["mesh"] = spec
    with _manifest_lock:
        try:
            existing = load_manifest(cache_dir) or []
            keys = {_program_key(p) for p in existing}
            if _program_key(prog) in keys:
                return
            existing.append(prog)
            os.makedirs(cache_dir, exist_ok=True)
            path = manifest_path(cache_dir)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(
                    {
                        "version": MANIFEST_VERSION,
                        "jaxlib": _jaxlib_stamp(),
                        "programs": existing,
                    },
                    fh,
                    indent=1,
                    sort_keys=True,
                )
            os.replace(tmp, path)
        except Exception:
            # Recording is best-effort: a read-only cache dir costs the
            # next spawn a compile, never this member its boot.
            log.warning("could not record prewarm program %r in %s",
                        prog, cache_dir, exc_info=True)
