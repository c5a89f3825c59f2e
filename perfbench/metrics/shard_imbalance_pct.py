"""How unevenly the sharded sweep loads the chips: (max - mean) / max
of the per-chip device-busy seconds in the traced window."""


def read(ctx):
    busy = ctx["busy_s"]
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - sum(busy) / len(busy)) / max(busy)
