"""Share of the rows the service dispatched in the window that carried
a request: (dispatched rows - phantom rows) / dispatched rows, from the
profile's rows and the ``serve_phantom_rows`` counter."""


def read(ctx):
    layer = ctx["layer"]
    rows = layer.get("dispatched_rows")
    if not rows:
        return None
    return 100.0 * (rows - layer["phantom_rows"]) / rows
