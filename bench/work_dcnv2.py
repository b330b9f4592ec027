"""Work counts of DLRM-DCNv2, from the configuration's shapes alone
(never from compiled HLO: a roofline's denominator must not move when
the implementation does)."""
from __future__ import annotations

from bench.reference_dcnv2 import mlp_dims


def dense_flops_per_query(cfg: dict) -> int:
    """Two FLOP (multiply, add) per weight of the dense part per query:
    the bottom MLP, each cross layer's V and W, the top MLP. Biases, the
    cross layers' x0 * (.) + x_l and the ReLUs are left out (under 0.1%).
    At MLPerf's widths: 2 * (170,496 + 3 * 3,538,944 + 5,243,136) =
    32,060,928."""
    bottom, top = mlp_dims(cfg)
    d0, rank = top[0], cfg["dcn_rank"]
    macs = sum(a * b for dims in (bottom, top)
               for a, b in zip(dims[:-1], dims[1:]))
    macs += cfg["dcn_layers"] * 2 * d0 * rank
    return 2 * macs


def bag_bytes_per_chip(cfg: dict, batch: int, chips: int) -> int:
    """Bytes one chip's bags have to move for one batch, with every
    table row-sharded over `chips`: its share of the looked-up rows read
    (float32; ids spread over the shards), its partial bags of the whole
    batch written, and every id read (each chip sees them all). Batch
    2048 on 4 chips at MLPerf's widths: 56,098,816 + 27,262,976 +
    1,753,088 = 85,114,880 B."""
    ids = batch * sum(cfg["multi_hot"])
    dim = cfg["emb_dim"]
    return (ids * dim * 4 // chips + batch * cfg["n_tables"] * dim * 4
            + ids * 4)
