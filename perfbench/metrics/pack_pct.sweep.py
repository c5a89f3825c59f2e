"""Share of the window the sweep planner spent packing buckets on the
host (sum of ``BucketProfile.pack_s`` over the window's buckets)."""


def read(ctx):
    layer = ctx["layer"]
    if "pack_s" not in layer:
        return None
    return 100.0 * layer["pack_s"] / layer["window_s"]
