"""The tiny benchmark root (`tiny.make_root`) writes a tiny file for every
configuration and mix BENCHMARK.json names. It predates the DLRM-DCNv2
cell, so its tiny configuration and mix are registered here, before any
test of this directory builds a root."""
from bench.tests import tiny

# DLRM-DCNv2's structure at CPU size: ragged tables (one on all 4 row
# shards, some on shard 0 alone), bags of 1 to 5 ids, 13 dense features.
tiny.CONFIGS.setdefault("dlrm-dcnv2-criteo1tb", {
    "model": "dlrm", "n_tables": 5, "emb_dim": 8,
    "table_rows": [1000, 3, 130, 600, 40], "multi_hot": [3, 1, 2, 5, 1],
    "dense_features": 13, "bottom_dims": [16, 8], "interaction": "dcnv2",
    "dcn_layers": 2, "dcn_rank": 4, "fc_dims": [16, 8], "out_dim": 1,
    "dtype": "float32", "matmul_precision": "highest",
    "mesh": {"shape": [1, 1, 4], "axes": ["pod", "data", "model"]}})
tiny.MIXES.setdefault("b2048-multihot-zipf", dict(
    tiny.DLRM_MIX, driver="dlrm_dcnv2_serve",
    ids={"dist": "zipf", "alpha": 1.05},
    dense={"dist": "uniform", "high": 4.0}))
