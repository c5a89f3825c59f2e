"""Share of the traced window the stepper's fetch spent turning fetched
arrays into results on the host: the self time of the program's
``repro.engine.results`` spans over the window."""


def read(ctx):
    trace = ctx["trace"]
    self_s = trace.get("span_self_s")
    if not self_s or not trace["window_s"]:
        return None
    return 100.0 * self_s.get("repro.engine.results", 0.0) \
        / trace["window_s"]
