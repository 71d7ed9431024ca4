"""traffic generator: how late the publishers ran (publish done - due)."""


def read(ctx):
    late = sorted(ctx["late_s"])
    if not late:
        return None
    return late[min(len(late) - 1, int(0.95 * len(late)))] * 1000.0
