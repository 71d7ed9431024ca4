"""Device time by stage, from the program's own scopes.

A device trace names an operation by its HLO instruction (``%fusion.2742``,
``%while.127``): 86-88% of a stream head's device time is one such name and
the names under it say nothing of the stage they belong to. The compiled
program knows: every instruction of its optimised HLO carries the
``jax.named_scope`` path it was traced under (``metadata={op_name=
"jit(stream_step)/head_prefill/while/body/.../head_moe/moe_experts/..."}``),
and the chip's trace shares that numbering. This module joins the two:

- :func:`stage_map` — ``{instruction: scope path}`` of one executable, the
  path being the program's DECLARED scope names (:data:`SCOPES`, the one
  list) found in the instruction's ``op_name``, outermost first.
- :func:`stage_seconds` — the one reducer, for profile bundles
  (obs/prof.py ``stages.json``) and the benchmark's per-layer metrics
  alike: device SELF time of ``(name, start, duration)`` events by scope
  path, a program's run at a time.
- the registry — ``register`` costs one dict entry a compile (engine/
  runner.py ``_TimedStep``); a map is seconds of HLO text and is built only
  when asked for (:func:`built`): by a profile bundle, and by
  ``InferenceEngine.stop()`` under ``cfg.stage_trace`` for the programs
  that ran. What is left behind is plain data: no executable is kept alive
  from here (the registry holds weak references, dropped once a map is
  built).

Attribution is BY ROOT: XLA fuses across scope boundaries and a fusion
carries the ``op_name`` of its root instruction, so a fusion's whole time
goes to its root's stage. A compile cache's key ignores metadata: a program
loaded from a cache that an older build wrote carries that build's scope
names, which shows as a large unscoped share (``()``).

jax inside functions (CLAUDE.md): importable from the control plane.
"""

from __future__ import annotations

import bisect
import io
import threading
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["SCOPES", "stage_map", "stage_seconds", "run_stage_seconds",
           "pair_runs", "read_device_lines", "bundle_stages", "register",
           "built", "clear"]

# Every ``jax.named_scope`` the serving programs declare, in the order a
# step runs them. A path holds only these names; whatever else an
# ``op_name`` carries (flax module names, ``while``/``body``, primitive
# names) is left out.
SCOPES = (
    # ops/preprocess.py
    "pre_cast_scale", "pre_resize", "pre_normalize",
    # engine/runner.py: the device window of a clip model, the classifier's
    # top-k
    "window_write", "window_copy", "softmax_topk",
    # models/vit.py, videomae.py, transformer.py: the encoder
    "embed", "encoder_block", "cls_head",
    # models/stream_head.py: the round of a stream head
    "head_seed", "head_prefill", "head_connector", "head_dense_mlp",
    "head_decode", "head_sample", "head_flush",
    # models/lfm2.py, gqa.py, mla.py, xing4.py, deepseek_v2.py,
    # falcon_h1.py, mamba2.py, nemotron_h.py: a head's layers
    "head_conv", "head_attn", "mla_prefill", "mla_decode", "mhc_maps",
    "head_moe", "head_ssm", "head_lm", "mtp_draft",
    # ops/ssd.py: the state-space scan inside a mixer
    "ssm_scan",
    # models/transformer.py: the expert layer (``moe_latent``: the two
    # projections of an expert layer whose experts live in a latent)
    "moe_route", "moe_latent", "moe_experts", "moe_shared",
)
_SCOPE_SET = frozenset(SCOPES)

Path = Tuple[str, ...]
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# a step's device event may read this much before the call that launched
# it (the trace's clock is set to the program's to within a few ms)
CLOCK_SLACK_S = 0.05


# -- the map of one program ------------------------------------------------

def _name_at(body: str, start: int) -> str:
    """The ``%name`` that starts at ``body[start]``."""
    end = start + 1
    while end < len(body) and body[end] not in ", }\n":
        end += 1
    return body[start:end]


def _parse(text: str) -> Tuple[str, Dict[str, Path]]:
    """(module name, {instruction: scope path}) of an optimised HLO text.

    One pass over the lines (a computation is printed before its callers,
    an instruction after its operands). An instruction without a scope of
    its own that calls a computation (a fusion) takes that computation's
    root's path, or the path of the last scoped instruction before the root
    where the root has none (a tuple of several outputs). An instruction
    the COMPILER made (no ``op_name``, or one that is no path under
    ``jit(...)``: a layout copy, the Mosaic call a ``ragged_dot`` becomes,
    whose ``op_name`` reads ``ragged-dot-none``) takes the path of its
    first operand that has one, else that of the loop whose body it
    stands in (what a scatter is expanded into runs under the scatter's
    scope); what the program traced outside every declared scope stays
    ``()``. The instructions inside fused computations are no
    events of a trace and are left out."""
    module = ""
    paths: Dict[str, Path] = {}
    of_op_name: Dict[str, Path] = {}
    stands_for: Dict[str, Path] = {}    # computation -> its root's path
    members: Dict[str, List[str]] = {}
    orphans: Dict[str, List[str]] = {}  # computation -> the compiler's own
    fused = set()
    comp, last = "", ()
    for line in io.StringIO(text):
        if line.startswith("  "):
            body = line.lstrip()
            root = body.startswith("ROOT ")
            if root:
                body = body[5:]
            cut = body.find(" = ")
            if cut < 0 or not body.startswith("%"):
                continue
            name = body[:cut]
            path: Path = ()
            traced = False
            at = body.find('op_name="', cut)
            if at >= 0:
                op_name = body[at + 9:body.find('"', at + 9)]
                traced = "/" in op_name
                path = of_op_name.get(op_name)
                if path is None:
                    path = of_op_name[op_name] = tuple(
                        p for p in op_name.split("/") if p in _SCOPE_SET)
            at = body.find(" calls=%", cut)
            if at >= 0:
                called = _name_at(body, at + 7)
                if " fusion(" in body[cut:at]:
                    fused.add(called)
                path = path or stands_for.get(called, ())
            if not path and not traced:
                at = body.find("(%", cut)
                if at >= 0:
                    for operand in body[at + 1:body.find(")", at)].split(
                            ", "):
                        path = paths.get(operand[operand.find("%"):], ())
                        if path:
                            break
                if not path:
                    orphans[comp].append(name)
            elif path:
                at = body.find(" body=%", cut)
                if at >= 0:         # a loop: its body's orphans are its own
                    for orphan in orphans.get(_name_at(body, at + 6), ()):
                        paths[orphan] = path
            paths[name] = path
            members[comp].append(name)
            last = path or last
            if root:
                stands_for[comp] = last
        elif line.startswith("HloModule "):
            module = line[10:].split(",", 1)[0].strip()
        elif line.endswith("{\n") and ") -> " in line:
            head = line[6:] if line.startswith("ENTRY ") else line
            comp, last = head.split(" ", 1)[0], ()
            members[comp], orphans[comp] = [], []
    for called in fused:
        for name in members.get(called, ()):
            paths.pop(name, None)
    return module, paths


def stage_map(compiled) -> Dict[str, Path]:
    """``{instruction name: scope path}`` of an AOT executable, from its
    optimised HLO: for every instruction of every computation a trace can
    show (the entry, loop bodies, branches; a fusion by its root), the
    tuple of the declared scope names (:data:`SCOPES`) in its ``op_name``,
    outermost first; ``()`` where it has none. Names are as a device trace
    prints them cut at ``" = "`` (``%fusion.57``)."""
    return _parse(compiled.as_text())[1]


# -- the reducer -----------------------------------------------------------

def run_stage_seconds(ops: Sequence[tuple], runs: Sequence[tuple]) -> list:
    """One ``{scope path: seconds}`` a run, in the order of ``runs`` =
    ``[(start, end, stage map)]`` (a run of a program is a module event;
    runs do not overlap). ``ops`` are the ``(name, start, duration)``
    events of one device line, on the runs' clock.

    SELF time: a ``while`` event encloses its body's events, so at every
    moment the time goes to the innermost event open then (one sorted
    sweep with a stack: n log n). By construction the paths' seconds,
    ``()`` included, add up to the union of the run's op intervals."""
    starts = [o[1] for o in ops]
    if any(a > b for a, b in zip(starts, starts[1:])):
        ops = sorted(ops, key=lambda o: o[1])
        starts = [o[1] for o in ops]
    end_of_run = ("", float("inf"), 0.0)
    out = []
    for run_start, run_end, paths in runs:
        # a parent starts no later than its children and ends no earlier
        events = sorted(ops[bisect.bisect_left(starts, run_start):
                            bisect.bisect_left(starts, run_end)],
                        key=lambda o: (o[1], -o[2]))
        events.append(end_of_run)
        acc: Dict[Path, float] = {}
        stack: list = []                # (end, path) of the events open now
        cursor = run_start
        for name, start, dur in events:
            # [cursor, start) goes to the innermost events open in it
            while stack and cursor < start:
                end, path = stack[-1]
                if end > cursor:
                    upto = end if end < start else start
                    acc[path] = acc.get(path, 0.0) + (upto - cursor)
                    cursor = upto
                if end <= start:
                    stack.pop()
            cursor = start
            stack.append((start + dur, paths.get(name, ())))
        out.append(acc)
    return out


def stage_seconds(ops: Sequence[tuple], maps_by_run: Sequence[tuple]
                  ) -> Dict[str, Optional[dict]]:
    """``{program id: {scope path: seconds, ..., "runs": n}}`` over the
    events ``ops`` of one device line. ``maps_by_run`` =
    ``[(start, end, program id, stage map | None)]``: which program a run
    is comes from the batch that launched it, never from the op names (two
    programs may both own a ``%fusion.57``). A program without a map (the
    jit fallback) reads None: nothing is guessed."""
    out: Dict[str, Optional[dict]] = {}
    known = [r for r in maps_by_run if r[3] is not None]
    for r in maps_by_run:
        out.setdefault(r[2], None if r[3] is None else {"runs": 0})
    for (_, _, program, _), acc in zip(known, run_stage_seconds(
            ops, [(s, e, m) for s, e, _, m in known])):
        total = out[program]
        total["runs"] += 1
        for path, seconds in acc.items():
            total[path] = total.get(path, 0.0) + seconds
    return out


def pair_runs(module_events: Sequence[tuple], calls: Sequence[tuple],
              module_of: Dict[str, str]) -> list:
    """``[(start, end, program id)]``: each step call ``(t_step0, t_fetched
    | None, program id)`` takes the first module event of its program's
    name (``module_of``; an event reads ``jit_raw(1234)``) not yet taken
    that starts after the call, less the clock slack, and before the
    batch's outputs were on the host where that is known. Calls and events
    are both in order on one device."""
    by_name: Dict[str, list] = {}
    for name, start, dur in sorted(module_events, key=lambda e: e[1]):
        by_name.setdefault(name.split("(", 1)[0], []).append(
            (start, start + dur))
    at: Dict[str, int] = {}
    out = []
    for t_step0, t_fetched, program in sorted(
            calls, key=lambda c: c[0]):
        name = module_of.get(program)
        events = by_name.get(name, ())
        i = at.get(name, 0)
        while i < len(events) and events[i][0] < t_step0 - CLOCK_SLACK_S:
            i += 1
        if i < len(events) and (t_fetched is None
                                or events[i][0] <= t_fetched):
            out.append(events[i] + (program,))
            i += 1
        at[name] = i
    return out


# -- a profile bundle's own trace --------------------------------------------

def read_device_lines(path: str) -> Optional[dict]:
    """``{"ops": [(name, start_s, dur_s)], "modules": [...]}`` of the first
    device plane of a ``.xplane.pb`` that has events on its ``XLA Ops`` /
    ``XLA Modules`` lines; None without one (a CPU capture). Times are
    seconds from the moment the trace was started; an operation's name is
    cut at ``" = "`` (the chip's trace names an op by its whole HLO
    line)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        dev = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            for ev in line.events:
                dev[key].append((ev.name.split(" = ", 1)[0],
                                 ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
        if dev["ops"] and dev["modules"]:
            return dev
    return None


def bundle_stages(xplane_path: str, t_trace0: float, span_events: list
                  ) -> Tuple[Optional[dict], str]:
    """(``stages.json``'s content, None) of one capture, or (None, why
    not).

    ``span_events`` are the bundle's lineage spans: the ``step_call``
    events of the ``engine.tick`` track carry the ``program`` their batch
    ran and end at ``ts`` (wall seconds) after ``dur_ms``, the ``fetch``
    events of ``engine.drain`` end when its outputs were on the host (both
    carry ``batch``); ``t_trace0`` is the wall time the trace was started
    at, which puts them on the device events' clock. Per program id: the runs found, and device ms a run by
    scope path (``/``-joined; the unscoped remainder under
    ``"unscoped"``)."""
    fetched = {tuple(ev["batch"]): ev["ts"] for ev in span_events
               if ev.get("stage") == "fetch" and ev.get("batch")}
    fetched = {batch: ts - t_trace0 for batch, ts in fetched.items()}
    calls = [(ev["ts"] - ev.get("dur_ms", 0.0) / 1e3 - t_trace0,
              fetched.get(tuple(ev.get("batch") or ())), ev["program"])
             for ev in span_events
             if ev.get("stage") == "step_call" and ev.get("program")]
    calls = [c for c in calls if c[0] >= 0.0]   # its event may be cut
    if not calls:
        return None, ("no step_call span names a program (spans off, or no "
                      "batch was dispatched during the capture)")
    dev = read_device_lines(xplane_path)
    if dev is None:
        return None, "the trace holds no device plane"
    maps = built({c[2] for c in calls})
    modules = dev["modules"]
    first_end = min(s + d for _, s, d in modules)
    last_launch = max(s for _, s, _ in modules)
    # (a run that follows no finished launch, or that no launch follows,
    # may be cut where the trace started or stopped: the profiler takes a
    # while to start, and a step launched meanwhile shows without its head)
    runs = [r for r in pair_runs(
        modules, calls,
        {p: m["module"] for p, m in maps.items() if m is not None})
        if first_end <= r[0] and r[1] <= last_launch]
    reduced = stage_seconds(
        dev["ops"], [(s, e, p, maps[p]["ops"]) for s, e, p in runs])
    programs = {}
    for program, total in reduced.items():
        n = total.pop("runs")
        programs[program] = {
            "runs": n,
            "device_ms_per_run": sum(total.values()) / n * 1e3,
            "stage_ms_per_run": {
                "/".join(path) or "unscoped": seconds / n * 1e3
                for path, seconds in sorted(
                    total.items(), key=lambda kv: -kv[1])},
        }
    return {"programs": programs,
            "no_map": sorted(p for p, m in maps.items() if m is None),
            "attribution": "by root: a fusion's time goes to the stage of "
                           "its root instruction"}, None


# -- the registry ------------------------------------------------------------

_lock = threading.Lock()
# program id -> what holds its executable; -> {"module", "ops"} | None
_steps: Dict[str, "weakref.ref"] = {}
_built: Dict[str, Optional[dict]] = {}


def register(program: str, step) -> None:
    """Note that ``step.compiled`` is (or will be) the AOT executable of
    ``program``. One dict entry; nothing is built. A program compiled anew
    under the same id forgets the map of the old one."""
    with _lock:
        _steps[program] = weakref.ref(step)
        _built.pop(program, None)


def built(programs=None) -> Dict[str, Optional[dict]]:
    """``{program id: {"module": HLO module name, "ops": stage map} |
    None}`` for ``programs`` (default: every program registered or built):
    the maps not built yet are built now, from the live executables, which
    are then let go. None for a program served through the jit fallback
    (it has no executable to read), and for one never registered."""
    with _lock:
        names = sorted(set(programs if programs is not None
                           else list(_steps) + list(_built)))
        todo = {p: _steps.pop(p) for p in names
                if p not in _built and p in _steps}
    fresh = {}
    for program, ref in todo.items():       # seconds of text each: unlocked
        compiled = getattr(ref(), "compiled", None)
        fresh[program] = None
        if compiled is not None:
            module, ops = _parse(compiled.as_text())
            fresh[program] = {"module": module, "ops": ops}
    with _lock:
        _built.update(fresh)
        return {p: _built.get(p) for p in names}


def clear() -> None:
    """Forget every program (tests)."""
    with _lock:
        _steps.clear()
        _built.clear()
