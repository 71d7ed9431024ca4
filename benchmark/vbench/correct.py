"""What decides ``correct``: the served results against the plain reference.

Compared, from what the timed window itself emitted: every result's routing
(exact), and for a seeded sample of results what the model's family keeps
of a result against the float32 reference run over the very frames that
camera published — which covers the collector's window assembly (the frames
it read, in order: its own spans say which those were), the device
preprocess, the model and what follows it in one comparison.

Numbers (each has its limit in the configuration file, set from readings
in PERF.md section 2):

- ``misrouted``   results whose (device, packet, timestamp, model) is not
                  one that was published for that camera; limit 0.
- the family's own (``families/<name>.py``, ``compare``), named after the
  model: for the two classifier families ``logprob_err_<model>``, the
  widest |log(served probability) - reference log-softmax| over the served
  top-5 of that model's sample, and ``logprob_mean_<model>``, the mean of
  the same: steady from seed to seed where the widest swings.
"""

from __future__ import annotations

import numpy as np

from . import loader
from . import traffic as traffic_mod


def routing_errors(received: list, cams: list, role_model: dict,
                   stamp_of) -> int:
    """Results that name a frame nobody published, the wrong model, the
    wrong timestamp, or a frame already answered."""
    by_id = {c[1]: c for c in cams}
    seen, bad = set(), 0
    for r in received:
        cam = by_id.get(r["device_id"])
        key = (r["device_id"], r["packet"])
        if (cam is None or key in seen
                or r["model"] != role_model[cam[2]]
                or r["timestamp"] != stamp_of(cam[0], r["packet"])):
            bad += 1
        seen.add(key)
    return bad


def reads_by_camera(events: list) -> dict:
    """{device_id: [packet, ...]} in the order the collector read them,
    from the program's ``collect`` lineage spans (one per frame read when
    the span recorder samples every frame)."""
    out = {}
    for e in sorted((e for e in events if e["stage"] == "collect"),
                    key=lambda e: e["ts"]):
        out.setdefault(e["stream"], []).append(e["frame"])
    return out


def unanswered(events: list, results: list, cams: list, sample_frames: dict,
               t_start: float, t_end: float, wall_minus_mono: float) -> int:
    """Frames the engine took and never answered, by the window's close.

    Every frame the collector reads from a camera that has been read as
    often as its model's sample has frames (``sample_frames``, by camera)
    is owed a result. Counted: a ``dropped`` span inside the window; a read
    inside the window that is still unanswered at the close although it is
    older than twice the longest read-to-result time the window saw (the
    younger ones are in flight); and, where the window is long enough to
    tell, a camera the collector did not read once in that time (paused or
    starved: its frames are published all the same)."""
    got = {(r["device_id"], r["packet"]): r["t"] for r in results}
    reads, span = {}, 0.0
    for e in events:
        if e["stage"] != "collect":
            continue
        t = e["ts"] - wall_minus_mono
        reads.setdefault(e["stream"], []).append((t, e["frame"]))
        done = got.get((e["stream"], e["frame"]))
        if done is not None and t_start <= done < t_end:
            span = max(span, done - t)
    horizon = t_end - 2.0 * span
    bad = sum(1 for e in events if e["stage"] == "dropped"
              and t_start <= e["ts"] - wall_minus_mono < t_end)
    for c in cams:
        seen = sorted(reads.get(c[1], []))
        owed = [(t, k) for i, (t, k) in enumerate(seen)
                if i + 1 >= sample_frames[c[1]] and t_start <= t < horizon]
        # the engine comes round to every camera within one read-to-result
        # time, so a stretch of two of them has to hold a read
        if not owed and horizon - t_start >= 2.0 * span:
            bad += 1
        bad += sum(1 for _, k in owed if (c[1], k) not in got)
    return bad


def last_reads(result: dict, reads: list, n: int):
    """The camera's last ``n`` READ frames, the answered one last: what a
    model without state was given. ``None`` where the spans do not show
    the result's frame, or the camera had read fewer than ``n`` by then."""
    if result["packet"] not in reads:
        return None
    at = reads.index(result["packet"])
    return reads[at + 1 - n:at + 1] if at + 1 >= n else None


def eligible(results: list, reads: dict, model_of_camera: dict) -> list:
    """``results`` whose input the harness can rebuild, each with
    ``window``: the frame numbers its answer depends on.

    The bus is latest-wins, so most published frames are never read: what
    a camera's model was given is among the frames the collector READ, and
    the reads are what its spans say. Which of them a result depends on is
    the family's answer (``window``); a result it gives no window for is
    not compared."""
    out = []
    for r in results:
        m = model_of_camera[r["device_id"]]
        window = loader.family(m["family"]).window(
            r, reads.get(r["device_id"], []), m["sizes"])
        if window:
            out.append(dict(r, window=window))
    return out


def draw_sample(results: list, per_model: int, seed: int) -> list:
    """Up to ``per_model`` results of each model, drawn from the seed."""
    rng = np.random.default_rng([int(seed), 1 << 41])
    out = []
    for model in sorted({r["model"] for r in results}):
        pool = [r for r in results if r["model"] == model]
        pick = rng.permutation(len(pool))[:per_model]
        out.extend(pool[i] for i in sorted(pick))
    return out


def reference_rows(sample: list, cams: list, seed: int, model_of: dict,
                   weights: dict, load_reference, quant: str = "") -> list:
    """The float32 reference's output for each sampled result, in order
    (for a classifier one logit row)."""
    import jax

    by_id = {c[1]: c for c in cams}
    rows = [None] * len(sample)
    for model in sorted({r["model"] for r in sample}):
        m = model_of[model]
        fam = loader.family(m["family"])
        fwd = load_reference(m["reference"]).jitted(
            m["family"], loader.frozen(m["sizes"]), quant)
        idx = [i for i, r in enumerate(sample) if r["model"] == model]
        block = fam.REFERENCE_BLOCK
        frames = max(len(sample[i]["window"]) for i in idx)
        # one host buffer a model, written in place block after block (one
        # compiled shape; rows past the last block's end keep old frames):
        # fresh pages cost this host ~1 s a GB, and 32 clips are 1.6 GB
        buf = None
        for at in range(0, len(idx), block):
            part = idx[at:at + block]
            for row, i in enumerate(part):
                r = sample[i]
                cam, _, _, h, w, _ = by_id[r["device_id"]]
                if buf is None:
                    buf = np.zeros((block, frames, h, w, 3), np.uint8)
                for t, j in enumerate(r["window"]):
                    traffic_mod.fill_frame(buf[row, t], seed, cam, j)
            out = jax.device_get(
                fwd(weights[model], *fam.reference_args(
                    buf, [sample[i]["window"] for i in part], m["sizes"])))
            for j, i in enumerate(part):
                rows[i] = jax.tree_util.tree_map(lambda a: a[j], out)
    return rows


def log_softmax(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    m = x.max()
    return x - m - np.log(np.exp(x - m).sum())


def topk(logits: np.ndarray, k: int = 5) -> list:
    """[(class, probability)] as a served result carries them."""
    lp = log_softmax(logits)
    ids = np.argsort(-lp, kind="stable")[:k]
    return [(int(i), float(np.exp(lp[i]))) for i in ids]


def compare(served: list, rows: list, models: list, model_of: dict) -> dict:
    """The named numbers of every model in ``models`` (one name a sampled
    result): its family's ``compare`` over what was kept of that model's
    results and the reference's rows for them."""
    out = {}
    for model in sorted(set(models)):
        mine = [i for i, name in enumerate(models) if name == model]
        out.update(loader.family(model_of[model]["family"]).compare(
            [served[i] for i in mine], [rows[i] for i in mine], model))
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number that has a limit
    in the configuration file within it; a limit with no number fails."""
    out, ok = {}, True
    for name in limits:
        value = numbers.get(name, 1e30)     # e.g. a model never sampled
        limit = limits[name]["limit"]
        out[name] = {"value": value, "limit": limit}
        if not value <= limit:     # NaN fails too
            ok = False
    return ok, out
