"""Share of the row-waves the device stepped in which a row had already
finished and waited for the slowest row of its shard: 1 - row_waves /
row_slots over the window's buckets (``BucketProfile``)."""


def read(ctx):
    trace = ctx["trace"]
    slots = trace.get("row_slots")
    if not slots:
        return None
    return 100.0 * (1.0 - trace["row_waves"] / slots)
