"""Device time of the engine's reduce-scatter of the partial bags per
batch (ms): the trace's operations under an `engine.*` scope (the
driver's `dist` class), averaged over the chips, over the batches
served in the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or "dist" not in tr["classes_s"]:
        return None
    return tr["classes_s"]["dist"] / ctx["window"]["batches"] * 1e3
