"""Device time of the FC stack per batch (ms): the trace's operations in
the fc class (`bench.opclass`), over the batches served in the traced
window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or "fc" not in tr["classes_s"]:
        return None
    return tr["classes_s"]["fc"] / ctx["window"]["batches"] * 1e3
