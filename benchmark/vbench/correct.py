"""What decides ``correct``: the served results against the plain reference.

Compared, from what the timed window itself emitted: every result's routing
(exact), and for a seeded sample of results the served top-5 against the
float32 reference run over the very frames that camera published — which
covers the collector's clip assembly (the last 8 frames it read, in
order: its own spans say which those were), the
device preprocess, the encoder, softmax and top-k in one comparison.

Numbers (each has its limit in the configuration file, set from readings
in PERF.md section 2):

- ``misrouted``   results whose (device, packet, timestamp, model) is not
                  one that was published for that camera; limit 0.
- ``logprob_err_<model>``  widest |log(served probability) - reference
                  log-softmax| over the served top-5 of that model's sample.
- ``logprob_mean_<model>`` the mean of the same: steady from seed to seed
                  where the widest swings.
"""

from __future__ import annotations

import numpy as np

from . import traffic as traffic_mod

CLIP_BLOCK = 4      # clips per reference call (8 x 1080p frames each)
FRAME_BLOCK = 16    # single frames per reference call


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


def unanswered(events: list, results: list, cams: list, clip_len: dict,
               t_start: float, t_end: float, wall_minus_mono: float) -> int:
    """Frames the engine took and never answered, by the window's close.

    Every frame the collector reads from a camera whose clip window is full
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
                if i + 1 >= max(clip_len[c[1]], 1) and t_start <= t < horizon]
        # the engine comes round to every camera within one read-to-result
        # time, so a stretch of two of them has to hold a read
        if not owed and horizon - t_start >= 2.0 * span:
            bad += 1
        bad += sum(1 for _, k in owed if (c[1], k) not in got)
    return bad


def eligible(results: list, reads: dict, clip_len: dict) -> list:
    """``results`` whose input the harness can rebuild, each with
    ``window``: the frame numbers the model was given.

    The bus is latest-wins, so most published frames are never read: a
    clip result's window is its camera's last ``n`` READ frames, the
    answered one last, and the reads are what the collector's spans say. A
    result whose frame the spans do not show, or whose camera had read
    fewer than ``n`` frames by then, is not compared."""
    out = []
    for r in results:
        n = max(clip_len[r["device_id"]], 1)
        seen = reads.get(r["device_id"], [])
        if r["packet"] not in seen:
            continue
        at = seen.index(r["packet"])
        if at + 1 >= n:
            out.append(dict(r, window=seen[at + 1 - n:at + 1]))
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


def reference_logits(sample: list, cams: list, seed: int, model_of: dict,
                     weights: dict, load_reference, quant: str = "") -> list:
    """One float32 logit row per sampled result, in order."""
    import jax

    by_id = {c[1]: c for c in cams}
    rows = [None] * len(sample)
    for model in sorted({r["model"] for r in sample}):
        m = model_of[model]
        ref = load_reference(m["reference"])
        fwd = ref.jitted(m["family"], tuple(sorted(m["sizes"].items())),
                         quant)
        n_frames = m["sizes"].get("num_frames", 0) \
            if m["family"] == "videomae" else 0
        idx = [i for i, r in enumerate(sample) if r["model"] == model]
        block = CLIP_BLOCK if n_frames else FRAME_BLOCK
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
                    buf = np.zeros((block, max(n_frames, 1), h, w, 3),
                                   np.uint8)
                for t, j in enumerate(r["window"]):
                    traffic_mod.fill_frame(buf[row, t], seed, cam, j)
            batch = buf if n_frames else buf[:, 0]
            out = np.asarray(jax.device_get(fwd(weights[model], batch)))
            for j, i in enumerate(part):
                rows[i] = out[j]
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


def compare(served_tops: list, ref_rows: list, models: list) -> dict:
    """Per model, from served top-k lists and reference rows:
    ``logprob_err_<model>`` the widest and ``logprob_mean_<model>`` the mean
    |log(served probability) - reference log-softmax| over the top-5 of that
    model's sampled results. (``top1_gap``, the widest gap by which a served
    top-1's reference logit lies below the reference's best, is returned too
    but carries no limit: it is 0 unless two classes tie within the rounding,
    and the float8 control reads as low as sound runs do; PERF.md.)"""
    out, gap = {}, 0.0
    for model in sorted(set(models)):
        errs = []
        for top, row, m in zip(served_tops, ref_rows, models):
            if m != model:
                continue
            lp = log_softmax(row)
            if not top:
                errs.append(1e30)
                continue
            if 0 <= top[0][0] < len(row):
                gap = max(gap, float(row.max() - row[top[0][0]]))
            for cid, p in top:
                if not 0 <= cid < len(row) or not p > 0:
                    errs.append(1e30)
                else:
                    errs.append(abs(float(np.log(p)) - float(lp[cid])))
        out[f"logprob_err_{model}"] = max(errs)
        out[f"logprob_mean_{model}"] = float(np.mean(errs))
    out["top1_gap"] = gap
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
