"""Bus bandwidth of the 4 MiB calls against the chip's ICI peak (%): bus
bytes (nccl-tests' definition, `bench.work.bus_bytes`) of the large
programs' executions in the trace, over their device spans, over the
peak of the chips' interconnect."""

from bench.work import bus_bytes


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None:
        return None
    span, moved = 0.0, 0.0
    n, chain = ctx["work"]["ranks"], ctx["work"]["chain"]
    for p in ctx["work"]["programs"]:
        mod = tr["modules"].get(p["module"])
        if p["cls"] == "large" and mod:
            span += mod["span_s"]
            moved += mod["count"] * chain * bus_bytes(p["name"], p["bytes"],
                                                      n)
    return moved / span / peaks["ici_bw"] * 100.0 if span else None
