"""Device-busy seconds per resolved scenario: the union of the device's
operation intervals in the traced window, summed over the chips used,
divided by the scenarios the window resolved."""


def read(ctx):
    busy, layer = ctx["busy_s"], ctx["layer"]
    if not busy or not layer.get("scenarios"):
        return None
    return sum(busy) / layer["scenarios"]
