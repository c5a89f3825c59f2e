"""Share of the traced window in which the device ran nothing and no
span of the program was open, averaged over the chips used: the idle
time the program's ``repro.*`` spans leave unexplained."""


def read(ctx):
    trace = ctx["trace"]
    idle = trace.get("idle_unattributed_s")
    if not trace.get("span_self_s") or not idle or not trace["window_s"]:
        return None
    used = [idle[k] for k in sorted(idle)][:ctx["chips"]]
    return 100.0 * sum(used) / len(used) / trace["window_s"]
