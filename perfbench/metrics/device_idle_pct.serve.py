"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(ctx):
    busy, trace = ctx["busy_s"], ctx["trace"]
    if not busy or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace["window_s"])
