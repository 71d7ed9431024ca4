"""device: share of the traced window in which no operation ran."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / ctx["seconds"])
