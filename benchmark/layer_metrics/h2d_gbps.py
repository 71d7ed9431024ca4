"""prefetch: bytes placed / seconds the transfer thread spent placing them
(vep_h2d_bytes / vep_h2d_seconds over the window)."""


def read(ctx):
    h = ctx["h2d"]
    if not h["seconds"] > 0:
        return None
    return h["bytes"] / h["seconds"] / 1e9
