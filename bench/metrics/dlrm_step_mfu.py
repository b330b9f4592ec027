"""The whole serving step's share of the chips' peak (%): the FC stack's
FLOPs per query (`bench.work`) times the queries served per second in
the traced window, over the chips' bf16 peak."""


def read(ctx):
    peaks = ctx["peaks"]
    if peaks is None or ctx["trace"] is None:
        return None
    flops_s = ctx["work"]["flops_per_query"] * ctx["end_to_end"]["dlrm_qps"]
    return flops_s / (ctx["chips"] * peaks["bf16_flops"]) * 100.0
