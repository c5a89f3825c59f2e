"""Share of the traced window the sweep planner spent building buckets
on the host: the self time of the program's ``repro.sweep.build`` spans
(graph arrays, DAG validation, policy lookup) over the window."""


def read(ctx):
    trace = ctx["trace"]
    self_s = trace.get("span_self_s")
    if not self_s or not trace["window_s"]:
        return None
    return 100.0 * self_s.get("repro.sweep.build", 0.0) / trace["window_s"]
