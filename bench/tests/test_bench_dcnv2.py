"""The DLRM-DCNv2 cell (`dlrm4.dcnv2-b2048-zipf`) at a tiny size on 4 of
the CPU's virtual devices: whole runs, untraced and traced, through
`bench/run.py`; the comparison that decides `correct` failing the
control and planted faults; its data, reference and work counts."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bench import control_dcnv2, reference_dcnv2, run, work_dcnv2
from bench.tests import tiny

CELL = "dlrm4.dcnv2-b2048-zipf"
SEEDS = (2**33 + 4242, 3_100_007_919)
REAL_CFG = os.path.join(tiny.REPO, "bench", "configs",
                        "dlrm-dcnv2-criteo1tb.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def tiny_cfg(root):
    with open(os.path.join(root, "bench", "configs",
                           "dlrm-dcnv2-criteo1tb.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("seed", SEEDS)
def test_cell_runs_and_is_correct(root, capsys, trace, seed):
    rc = run.main(tiny.argv(CELL, seed, trace), root=root, src=tiny.SRC,
                  require_chip=False, cache=False)
    assert rc == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["count"] == 4
    assert r["checks"]["logit_gap"]["value"] <= \
        r["checks"]["logit_gap"]["limit"]
    if trace:
        # no device trace on the CPU: the per-layer metrics read from it
        # are left out, never reported as 0
        assert set(r["metrics"]) == {"compile_s"}
    else:
        assert set(r["metrics"]) == {"dlrm_qps", "dlrm_p95_ms", "setup_s"}
        assert all(m["value"] > 0 for m in r["metrics"].values())


def _rebuild(drv, body):
    """The driver's serving step replaced by `body(params, ids, dense,
    ctx)` under the same shard_map."""
    from repro.configs.base import ParallelConfig
    from repro.core import CollectiveEngine
    from repro.parallel.ops import ParCtx
    ctx = ParCtx(engine=CollectiveEngine(drv.mesh), pcfg=ParallelConfig(),
                 mesh=drv.mesh)
    drv.serve = jax.jit(jax.shard_map(
        lambda p, i, d: body(p, i, d, ctx), mesh=drv.mesh,
        in_specs=(drv.specs, P(None, None), P("model", None)),
        out_specs=P("model", None), check_vma=False))


def _dropped_cross_layer(drv):
    from repro.models import dlrm as dm
    _rebuild(drv, lambda p, i, d, ctx: dm.dlrm_forward(
        dict(p, cross=p["cross"][:-1]), i, ctx, dense=d, cfg=drv.mcfg))


def _bags_from_shard_0(drv):
    from repro.models import dlrm as dm

    def body(p, i, d, ctx):
        own = (jax.lax.axis_index("model") == 0).astype(jnp.float32)
        return dm.dlrm_forward(dict(p, tables=p["tables"] * own), i, ctx,
                               dense=d, cfg=drv.mcfg)
    _rebuild(drv, body)


@pytest.mark.parametrize("fault", [_dropped_cross_layer, _bags_from_shard_0,
                                   control_dcnv2.install])
def test_faults_and_the_control_are_not_correct(root, fault):
    r = tiny.run_cell(root, CELL, SEEDS[0], patch=fault)
    assert r["correct"] is False
    gap = r["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_no_exchange_is_not_correct(root, monkeypatch):
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)
    r = tiny.run_cell(root, CELL, SEEDS[1])
    assert r["correct"] is False


def test_device_tables_are_the_references_rows(tiny_cfg):
    """The driver's packed tables, made on 4 devices, hold row r of table
    t where `reference_dcnv2` defines it; padding rows are never read."""
    from repro.models import dlrm as dm
    drv = run._load_module(os.path.join(tiny.REPO, "bench", "drivers",
                                        "dlrm_dcnv2_serve.py"), "drv_test")
    d = drv.Driver(tiny_cfg, tiny.MIXES["b2048-multihot-zipf"],
                   jax.devices()[:4])
    keys = reference_dcnv2.keys(SEEDS[0], tiny_cfg)
    params = d.make_params(keys)
    tables = dm.unpack_tables(np.asarray(params["tables"]), d.mcfg, 4)
    for t, tab in enumerate(tables):
        rows = np.arange(tab.shape[0])
        want = reference_dcnv2.table_rows_np(keys[0], t, rows,
                                             tiny_cfg["emb_dim"])
        np.testing.assert_array_equal(np.asarray(tab), want)
    w = reference_dcnv2.dense_np(keys, tiny_cfg)
    np.testing.assert_array_equal(np.asarray(params["cross"][1]["v"]),
                                  w["cross"][1][0])
    np.testing.assert_array_equal(np.asarray(params["fc"][-1]["b"]),
                                  w["top"][-1][1])


def test_warm_uses_batches_both_pools_hold(tiny_cfg, monkeypatch):
    """The id and dense pools are chunked by their own widths: here 22
    batches asked for give 25 of ids and 24 of dense features, and the
    warm-up must stay within both."""
    drv = run._load_module(os.path.join(tiny.REPO, "bench", "drivers",
                                        "dlrm_dcnv2_serve.py"), "drv_test3")
    monkeypatch.setattr(drv, "POOL_CHUNK_BYTES", 2000)
    mix = dict(tiny.MIXES["b2048-multihot-zipf"], pool_batches_per_s=1)
    d = drv.Driver(tiny_cfg, mix, jax.devices()[:4])
    d.load(SEEDS[0], 20)
    assert (len(d.pool), len(d.dense)) == (25, 24)
    d.warm()


def test_every_per_layer_metric_reads_the_drivers_classes(root, tiny_cfg):
    """The driver classes the step's ops under the names the DLRM cells'
    readers read (`lookup`, `fc`, and `dist` for the engine), and its
    work counts under their keys: given a trace of those classes, every
    per-layer metric BENCHMARK.json lists for the cell reads a number."""
    from bench.peaks import PEAKS
    drv = run._load_module(os.path.join(tiny.REPO, "bench", "drivers",
                                        "dlrm_dcnv2_serve.py"), "drv_test4")
    d = drv.Driver(tiny_cfg, tiny.MIXES["b2048-multihot-zipf"],
                   jax.devices()[:4])
    d.load(SEEDS[0], 0.05)
    (classes,) = d.classes().values()
    assert {"lookup", "fc", "dist"} <= set(classes.values())
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ctx = {"trace": {"classes_s": {c: 0.01 for c in ("lookup", "fc",
                                                      "dist")},
                     "busy_s": 0.5, "window_s": 1.0},
           "end_to_end": {"dlrm_qps": 1000.0}, "work": d.work(),
           "chips": 4, "peaks": PEAKS["TPU v5 lite"], "compile_s": 1.0,
           "window": {"batches": 10}}
    got = run.read_per_layer(bench, CELL, ctx, root)
    assert set(got) == {m["name"] for m in bench["per_layer"]
                        if run.applies(m, CELL)}
    assert all(m["value"] > 0 for m in got.values())


def test_traffic_is_seeded_and_within_each_table(tiny_cfg):
    drv = run._load_module(os.path.join(tiny.REPO, "bench", "drivers",
                                        "dlrm_dcnv2_serve.py"), "drv_test2")
    rows, hots = tiny_cfg["table_rows"], tiny_cfg["multi_hot"]
    sample = drv.multihot_sampler(rows, hots, "zipf", 1.05)
    ids = [np.asarray(sample(drv.multihot_keys(s, len(hots)), np.uint32(0), 3,
                             64)).reshape(3, 64, sum(hots))
           for s in (SEEDS[0], SEEDS[0], SEEDS[1])]
    np.testing.assert_array_equal(ids[0], ids[1])
    assert not np.array_equal(ids[0], ids[2])
    tables = reference_dcnv2.slot_tables(tiny_cfg)
    assert np.all(ids[0] >= 0)
    assert np.all(ids[0] < np.asarray(rows)[tables])
    big = ids[0][..., tables == 0].ravel()        # 1000 rows, Zipf(1.05)
    assert np.bincount(big).max() > 10 * len(big) / rows[0]   # hot rows
    dense = drv.dense_sampler(13, 4.0)(np.uint32(7), np.uint32(0), 2, 8)
    dense = np.asarray(dense)
    assert dense.shape == (2, 8 * 13) and dense.min() >= 0 \
        and dense.max() < 4


def test_work_counts_at_mlperf_widths():
    with open(REAL_CFG) as f:
        cfg = json.load(f)
    assert work_dcnv2.dense_flops_per_query(cfg) == 32_060_928
    assert work_dcnv2.bag_bytes_per_chip(cfg, 2048, 4) == 85_114_880
    assert sum(cfg["multi_hot"]) == 214
    published = cfg["published"]["table_rows"]
    assert sum(published) == cfg["published"]["rows"] == 204_184_588
    assert sum(cfg["table_rows"]) == 94_184_588
    assert [t for t, (a, b) in enumerate(zip(published, cfg["table_rows"]))
            if a != b] == [0, 9, 19, 20, 21]
