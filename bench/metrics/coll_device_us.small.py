"""Device time per collective call at the small sizes (us): the device
spans of the small programs' executions in the trace, over the calls
they made (executions times the chain length)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    span, calls = 0.0, 0
    for p in ctx["work"]["programs"]:
        mod = tr["modules"].get(p["module"])
        if p["cls"] == "small" and mod:
            span += mod["span_s"]
            calls += mod["count"] * ctx["work"]["chain"]
    return span / calls * 1e6 if calls else None
