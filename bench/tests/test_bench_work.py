"""Work counts and peaks against hand counts."""
import pytest

from bench import peaks, work

TABLE2 = {"n_tables": 100, "emb_dim": 32, "fc_dims": [2048, 512, 256],
          "out_dim": 1}


def test_dlrm_fc_counts():
    # 3200*2048 + 2048*512 + 512*256 + 256*1
    assert work.dlrm_fc_params(TABLE2) == 7_733_504
    assert work.dlrm_flops_per_query(TABLE2) == 15_467_008


def test_dlrm_lookup_bytes():
    # per query: 100 rows of 128 B read, 12,800 B written, 400 B of ids
    assert work.dlrm_lookup_bytes(TABLE2, 1) == 26_000
    assert work.dlrm_lookup_bytes(TABLE2, 256) == 6_656_000
    # 8.1 us at 819 GB/s
    assert work.dlrm_lookup_bytes(TABLE2, 256) / 819e9 == pytest.approx(
        8.127e-6, rel=1e-3)


@pytest.mark.parametrize("name,factor", [
    ("allreduce", 1.5), ("reduce_scatter", 0.75), ("alltoall", 0.75),
    ("allgather", 3.0), ("gather", 3.0), ("bcast", 1.0), ("reduce", 1.0)])
def test_bus_bytes_four_ranks(name, factor):
    assert work.bus_bytes(name, 4 << 20, 4) == factor * (4 << 20)


def test_bus_bytes_unknown():
    with pytest.raises(ValueError):
        work.bus_bytes("scan", 4, 4)


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bw"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bw"] == 200e9   # 1,600 Gbit/s
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")
