"""Device time of the embedding lookup per batch (ms): the trace's
operations that `bench.opclass` puts in the lookup class, over the
batches served in the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or "lookup" not in tr["classes_s"]:
        return None
    return tr["classes_s"]["lookup"] / ctx["window"]["batches"] * 1e3
