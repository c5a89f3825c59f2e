"""Device-busy milliseconds per lockstep wave: the device's busy time in
the traced window, summed over the chips used, divided by the waves the
window's buckets ran (``BucketProfile.waves``, summed)."""


def read(ctx):
    busy, waves = ctx["busy_s"], ctx["trace"].get("waves")
    if not busy or not waves:
        return None
    return 1000.0 * sum(busy) / waves
