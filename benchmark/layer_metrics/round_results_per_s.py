"""engine loop: results per second at the pace of the rounds, i.e. cameras
over the median time between one camera's consecutive answers. A count
over the window moves in steps of one round (64 results of ~400); this
moves with the round's length."""
import statistics


def read(ctx):
    times = {}
    for r in ctx["results"]:
        times.setdefault(r["device_id"], []).append(r["t"])
    gaps = [b - a for ts in times.values()
            for a, b in zip(sorted(ts), sorted(ts)[1:])]
    if not gaps:
        return None
    return len(times) / statistics.median(gaps)
