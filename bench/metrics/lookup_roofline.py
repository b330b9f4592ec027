"""The lookup's share of its roofline (%): the least time the chip could
take for the bytes the lookup has to move (`bench.work.dlrm_lookup_bytes`;
it does no arithmetic, so bandwidth bounds it) at the HBM peak, over the
lookup's device time."""


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or not tr["classes_s"].get("lookup"):
        return None
    per_batch_s = tr["classes_s"]["lookup"] / ctx["window"]["batches"]
    least_s = ctx["work"]["lookup_bytes"] / peaks["hbm_bw"]
    return least_s / per_batch_s * 100.0
