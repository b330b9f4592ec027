"""Distributed DLRM (paper use case 2) and DLRM-DCNv2 vs single-device
references."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.dlrm import dlrm_v2, reduced, reduced_v2
from repro.configs.base import ParallelConfig
from repro.core.engine import CollectiveEngine
from repro.core.topology import make_mesh
from repro.models import dlrm as dlrm_mod
from repro.models.common import Builder
from repro.parallel.ops import ParCtx


def test_dlrm_distributed_matches_reference(rng):
    cfg = reduced()
    mesh = make_mesh((1, 2, 4), ("pod", "data", "model"))
    eng = CollectiveEngine(mesh, backend="microcode")
    ctx = ParCtx(engine=eng, pcfg=ParallelConfig(), mesh=mesh)
    b = Builder("init", key=jax.random.PRNGKey(0), dtype=jnp.float32)
    params = dlrm_mod.dlrm_params(b, cfg, 4)
    specs = dlrm_mod.dlrm_specs(cfg, 4)
    B = 8
    idx = rng.integers(0, cfg.rows_per_table, (B, cfg.n_tables)).astype(np.int32)

    g = jax.jit(jax.shard_map(
        lambda p, i: dlrm_mod.dlrm_forward(p, i, ctx),
        mesh=mesh, in_specs=(specs, P(("pod", "data"), None)),
        out_specs=P(("pod", "data"), None), check_vma=False))
    out = np.asarray(g(params, jnp.asarray(idx)))
    ref = np.asarray(dlrm_mod.dlrm_reference(params, jnp.asarray(idx)))
    np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)


def test_dlrm_pallas_lookup_matches(rng):
    cfg = reduced()
    mesh = make_mesh((1, 1, 2), ("pod", "data", "model"))
    eng = CollectiveEngine(mesh, backend="microcode")
    ctx = ParCtx(engine=eng, pcfg=ParallelConfig(), mesh=mesh)
    b = Builder("init", key=jax.random.PRNGKey(1), dtype=jnp.float32)
    params = dlrm_mod.dlrm_params(b, cfg, 2)
    specs = dlrm_mod.dlrm_specs(cfg, 2)
    idx = rng.integers(0, cfg.rows_per_table, (4, cfg.n_tables)).astype(np.int32)

    outs = {}
    for use_pallas in (False, True):
        g = jax.jit(jax.shard_map(
            lambda p, i, up=use_pallas: dlrm_mod.embedding_lookup(
                p["tables"], i, ctx, use_pallas=up),
            mesh=mesh, in_specs=(specs, P(None, None)),
            out_specs=P(None, None), check_vma=False))
        outs[use_pallas] = np.asarray(g(params, jnp.asarray(idx)))
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-5)


# --------------------------------------------------------------------------
# DLRM-DCNv2 (MLPerf, Criteo 1TB multi-hot) at `reduced_v2()`, 4 row shards
# --------------------------------------------------------------------------

def _dcnv2_setup(rng, tp=4, batch=16):
    """Seeded random parameters (every leaf, biases too), ids drawn per
    table from its own rows, dense features; the 4-way model mesh."""
    cfg = reduced_v2()
    mesh = make_mesh((1, 1, tp), ("pod", "data", "model"))
    ctx = ParCtx(engine=CollectiveEngine(mesh, backend="microcode"),
                 pcfg=ParallelConfig(), mesh=mesh)
    shapes = dlrm_mod.dlrm_params(Builder("shape"), cfg, tp)
    leaves, tree = jax.tree.flatten(shapes)
    params = jax.tree.unflatten(tree, [
        jnp.asarray(rng.normal(0.0, 0.5, s.shape), jnp.float32)
        for s in leaves])
    ids = np.concatenate(
        [rng.integers(0, rows, (batch, m))
         for rows, m in zip(cfg.rows, cfg.hots)], axis=1).astype(np.int32)
    dense = rng.uniform(0.0, 4.0, (batch, cfg.dense_features)).astype(
        np.float32)
    return cfg, mesh, ctx, params, ids, dense


def _dcnv2_step(cfg, mesh, ctx, tp=4):
    specs = dlrm_mod.dlrm_specs(cfg, tp)
    return jax.jit(jax.shard_map(
        lambda p, i, d: dlrm_mod.dlrm_forward(p, i, ctx, dense=d, cfg=cfg),
        mesh=mesh, in_specs=(specs, P(None, None), P("model", None)),
        out_specs=P("model", None), check_vma=False))


def test_dlrm_dcnv2_sharded_matches_reference(rng):
    cfg, mesh, ctx, params, ids, dense = _dcnv2_setup(rng)
    out = np.asarray(_dcnv2_step(cfg, mesh, ctx)(params, ids, dense))
    tables = dlrm_mod.unpack_tables(params["tables"], cfg, 4)
    ref = np.asarray(dlrm_mod.dlrm_dcnv2_reference(
        tables, params, jnp.asarray(ids), jnp.asarray(dense), cfg))
    assert out.shape == (16, 1)
    # Both sides are float32. The program sums each bag in 4 partials and
    # then across chips, the reference in one pass: the bags differ in
    # the last bits, and each cross layer multiplies x0 in, so a rounding
    # there grows by |x0| per layer. Over seeds the gap reads 1e-7 to
    # 2.2e-7 of the largest logit; 1e-5 leaves ~50x. Dropping a cross
    # layer moves the logits by ~0.8 of it.
    scale = np.abs(ref).max()
    np.testing.assert_allclose(out, ref, atol=1e-5 * scale, rtol=0)


def test_dlrm_dcnv2_row_shards_add_up_to_the_pooled_bags(rng):
    """The share test: each row shard's partial bags, over the global
    batch, add up to the bags pooled from the whole tables."""
    cfg, _, _, params, ids, _ = _dcnv2_setup(rng)
    _, _, rows_l = dlrm_mod.table_layout(cfg, 4)
    packed = params["tables"]
    parts = [np.asarray(dlrm_mod.bag_partials(
        packed[r * rows_l:(r + 1) * rows_l], jnp.asarray(ids), cfg, 4, r))
        for r in range(4)]
    tables = [np.asarray(t) for t in dlrm_mod.unpack_tables(packed, cfg, 4)]
    starts = np.cumsum((0,) + cfg.hots[:-1])
    whole = np.concatenate([tab[ids[:, s:s + m]].sum(axis=1)
                            for tab, s, m in zip(tables, starts, cfg.hots)],
                           axis=1)
    assert all(np.abs(p).sum() > 0 for p in parts)   # every shard serves
    # float32 sums of at most 5 rows, in another order: a few ulp
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-6, atol=1e-6)
    # a table smaller than one 128-row shard lies on shard 0 alone
    t3 = slice(3 * cfg.emb_dim, 4 * cfg.emb_dim)      # rows 3, bag of 1
    assert cfg.rows[1] == 3
    t1 = slice(1 * cfg.emb_dim, 2 * cfg.emb_dim)
    assert all(np.all(p[:, t1] == 0) for p in parts[1:])
    assert np.abs(parts[0][:, t3]).sum() > 0


def test_dlrm_dcnv2_counts_its_work_when_built(rng):
    cfg, mesh, ctx, params, ids, dense = _dcnv2_setup(rng)
    _dcnv2_step(cfg, mesh, ctx).lower(params, ids, dense)
    _, _, rows_l = dlrm_mod.table_layout(cfg, 4)
    m = ctx.engine.metrics
    assert m.get("dlrm.ids_per_query") == sum(cfg.hots) == 12
    assert m.get("dlrm.table_rows_per_chip") == rows_l == 896
    assert m.get("dlrm.bag_collective_bytes") == 16 * 5 * 8 * 4


def test_dlrm_paper_config_path_unchanged(rng):
    """Table 2's model through the same entry with its config passed: the
    concat path, bit for bit what it gives without one, and the
    reference's answer."""
    cfg = reduced()
    mesh = make_mesh((1, 1, 4), ("pod", "data", "model"))
    ctx = ParCtx(engine=CollectiveEngine(mesh, backend="microcode"),
                 pcfg=ParallelConfig(), mesh=mesh)
    b = Builder("init", key=jax.random.PRNGKey(2), dtype=jnp.float32)
    params = dlrm_mod.dlrm_params(b, cfg, 4)
    specs = dlrm_mod.dlrm_specs(cfg, 4)
    idx = jnp.asarray(rng.integers(0, cfg.rows_per_table,
                                   (8, cfg.n_tables)).astype(np.int32))
    outs = []
    for kw in ({}, {"cfg": cfg}):
        g = jax.jit(jax.shard_map(
            lambda p, i, kw=kw: dlrm_mod.dlrm_forward(p, i, ctx, **kw),
            mesh=mesh, in_specs=(specs, P(None, None)),
            out_specs=P(None, None), check_vma=False))
        outs.append(np.asarray(g(params, idx)))
    np.testing.assert_array_equal(outs[0], outs[1])
    ref = np.asarray(dlrm_mod.dlrm_reference(params, idx))
    np.testing.assert_allclose(outs[1], ref, atol=1e-3, rtol=1e-3)


def test_dlrm_v2_config_is_mlperfs():
    cfg = dlrm_v2()
    assert sum(cfg.rows) == 204_184_588
    assert sum(cfg.rows) * cfg.emb_dim * 4 == 104_542_509_056
    assert cfg.ids_per_query == 214
    assert sum(m for r, m in zip(cfg.rows, cfg.hots) if r == 40_000_000) \
        == 149
    assert cfg.interaction_dim == 3456
    assert reduced().interaction_dim == 8 * 16


@pytest.mark.parametrize("bad", [
    {"multi_hot": (1, 2)},                                  # not 8 entries
    {"dense_features": 13},                                 # concat + dense
    {"multi_hot": (2,) * 8},                                # concat + bags
    {"interaction": "dot"},
    {"interaction": "dcnv2", "dense_features": 13, "bottom_dims": (16, 8),
     "dcn_layers": 1, "dcn_rank": 4},                       # bottom != 16
    {"interaction": "dcnv2", "dense_features": 13, "bottom_dims": (16,)},
])
def test_dlrm_config_rejects_what_no_path_runs(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(reduced(), **bad)


def test_dlrm_dcnv2_refuses_the_pallas_gather(rng):
    cfg, _, ctx, params, ids, dense = _dcnv2_setup(rng, tp=1)
    with pytest.raises(ValueError, match="Pallas"):
        dlrm_mod.dlrm_forward(params, ids, ctx, True, dense=dense, cfg=cfg)
