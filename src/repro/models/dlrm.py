"""Distributed DLRM inference — the paper's §6 use case, TPU-native, and
MLPerf's DLRM-DCNv2 on the same path.

Paper design (Fig. 15): embedding tables distributed over nodes 1-4,
FC1 checkerboard-decomposed over 8 nodes, FC2/FC3 pipelined on nodes 9/10,
all communication through ACCL+ streaming collectives.

TPU mapping of the paper's model (`interaction="concat"`) over the
(data, model) mesh:
  * tables shard over 'model' (the HBM-capacity argument is identical:
    50 GB of embeddings > 16 GB HBM/chip) — each rank holds a table slice
    and serves lookups for its rows (vocab-parallel gather + psum, exactly
    the embedding-node -> compute-node transmission of partial vectors);
  * FC1 is checkerboard (row+column) decomposed: columns over 'model'
    (each rank consumes its slice of the concat vector — the row partition)
    and the partial products reduce through the engine (the paper's
    "reduce slave" nodes) — matmul_reduce_scatter = FC1 + reduction fused;
  * FC2/FC3 column-parallel, batch streams over 'data' (the pipeline axis
    of nodes 9/10 becomes pure data parallelism — on a TPU mesh the
    all-reduce fabric replaces the point-to-point pipeline).

Requests are batched along 'data'; the Pallas embedding_gather kernel
serves the per-rank lookups when use_pallas is on.

DLRM-DCNv2 (`interaction="dcnv2"`, `configs/dlrm.dlrm_v2`): the ragged
tables are packed into one (tp * rows_local, dim) array sharded over
'model' on rows, each table split row-wise into 128-aligned shards
(`table_layout`), so every chip holds a 1/tp slice of every table. Each
chip sum-pools, for every sample of the global batch, the ids of each bag
that fall in its rows (`bag_partials`); one engine reduce-scatter over
'model' sums the partial bags and leaves each chip the pooled bags of its
B/tp samples; the bottom MLP, the low-rank cross network and the top MLP
then run data-parallel on those samples and their dense features.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.dlrm import DLRMConfig
from repro.core import telemetry
from repro.models.common import Builder, sharded_initializer
from repro.parallel.ops import ParCtx

# Rows per shard are a multiple of this, so the Pallas lookup reads every
# shard in place (kernels/embedding_gather.py: V on the 128 lanes).
ROW_ALIGN = 128


def table_layout(cfg: DLRMConfig, tp: int):
    """The packed DCNv2 table's layout over tp row shards: (rows of each
    table per shard, where each table starts in a shard's block, rows per
    shard). Table t's rows [s * per[t], (s + 1) * per[t]) lie in shard s
    at local rows [off[t], off[t] + per[t]); per[t] is the table's rows
    over tp rounded up to ROW_ALIGN, so a table of fewer rows than that
    lies on shard 0 alone."""
    per = tuple(-(-r // (tp * ROW_ALIGN)) * ROW_ALIGN for r in cfg.rows)
    off = tuple(itertools.accumulate((0,) + per[:-1]))
    return per, off, sum(per)


def dlrm_params(b: Builder, cfg: DLRMConfig, tp: int):
    """Tables stacked (T, rows, dim) sharded over model on rows; rows is
    rows_per_table rounded up to ROW_ALIGN per shard (ids stay below
    rows_per_table, so the tail rows are never looked up). DCNv2: see
    `dcnv2_params`."""
    if cfg.interaction == "dcnv2":
        return dcnv2_params(b, cfg, tp)
    align = tp * ROW_ALIGN
    rows = -(-cfg.rows_per_table // align) * align
    concat = cfg.n_tables * cfg.emb_dim
    p = {
        "tables": b.param((cfg.n_tables, rows, cfg.emb_dim),
                          P(None, "model", None), scale=0.01),
        "fc": [],
    }
    dims = (concat,) + tuple(cfg.fc_dims) + (cfg.out_dim,)
    fcs = []
    last = len(dims) - 2
    for i in range(len(dims) - 1):
        # FC1 checkerboard: in-dim over model (row partition of the concat
        # vector); middle FCs column-parallel; the tiny head replicates.
        if i == 0:
            spec = P("model", None)
        elif i < last:
            spec = P(None, "model")
        else:
            spec = P(None, None)
        fcs.append({
            "w": b.param((dims[i], dims[i + 1]), spec),
            "b": b.param((dims[i + 1],), P(None), init="zeros"),
        })
    p["fc"] = fcs
    return p


def dcnv2_params(b: Builder, cfg: DLRMConfig, tp: int):
    """DLRM-DCNv2: the packed tables (`table_layout`) sharded over model
    on rows; the bottom MLP, the cross layers (V: d0 x rank, no bias; W:
    rank x d0 with bias) and the top MLP (`fc`) replicated, as the dense
    part runs data-parallel."""
    _, _, rows_l = table_layout(cfg, tp)
    rep = P(None, None)

    def mlp(dims):
        return [{"w": b.param((m, n), rep),
                 "b": b.param((n,), P(None), init="zeros")}
                for m, n in zip(dims[:-1], dims[1:])]

    d0 = cfg.interaction_dim
    return {
        "tables": b.param((tp * rows_l, cfg.emb_dim), P("model", None),
                          scale=0.01),
        "bottom": mlp((cfg.dense_features,) + tuple(cfg.bottom_dims)),
        "cross": [{"v": b.param((d0, cfg.dcn_rank), rep),
                   "w": b.param((cfg.dcn_rank, d0), rep),
                   "b": b.param((d0,), P(None), init="zeros")}
                  for _ in range(cfg.dcn_layers)],
        "fc": mlp((d0,) + tuple(cfg.fc_dims) + (cfg.out_dim,)),
    }


def dlrm_specs(cfg: DLRMConfig, tp: int):
    return dlrm_params(Builder("spec"), cfg, tp)


def dlrm_initializer(cfg: DLRMConfig, mesh):
    """jit of PRNG key -> random parameters, made on the mesh shard by
    shard (`models.common.sharded_initializer`)."""
    tp = mesh.shape["model"]
    return sharded_initializer(lambda b: dlrm_params(b, cfg, tp), mesh,
                               dlrm_specs(cfg, tp))


def dlrm_init(cfg: DLRMConfig, mesh, seed: int = 0):
    return dlrm_initializer(cfg, mesh)(jax.random.PRNGKey(seed))


@telemetry.named_scope("dlrm.lookup")
def embedding_lookup(tables, indices, ctx: ParCtx, use_pallas: bool = False):
    """The paper's one-id-per-table lookup. tables: (T, rows_local, dim)
    local slice over 'model'; indices: (B, T) global row ids. Returns
    (B, T*dim) concat vector, replicated.

    Each rank gathers the rows it owns, with XLA's gather or, under
    `use_pallas`, `kernels/embedding_gather.py` (which reads the
    row-minor stack in place), and zeroes the ids it does not own: a
    partial vector. With tp > 1 one engine allreduce over 'model' sums
    the partials into the concat vector — the paper's partial-embedding
    transmission from memory nodes to compute nodes; with tp = 1 the
    partial is the whole vector and no collective runs. Runs under the
    device scope `dlrm.lookup` (the allreduce nests under it as
    `engine.*`). DCNv2's multi-hot bags: `bag_partials`.
    """
    t, rows_l, dim = tables.shape
    tp = ctx.tp
    lo = ctx.tp_rank() * rows_l
    local = indices.T - lo                       # (T, B)
    hit = (local >= 0) & (local < rows_l)
    safe = jnp.clip(local, 0, rows_l - 1)
    if use_pallas:
        from repro.kernels import ops as kops
        rows = kops.embedding_gather(tables, safe)  # (T, B, dim)
    else:
        rows = jax.vmap(lambda tab, ix: jnp.take(tab, ix, axis=0))(
            tables, safe)                         # (T, B, dim)
    rows = jnp.where(hit[..., None], rows, 0.0)
    vec = jnp.moveaxis(rows, 0, 1).reshape(indices.shape[0], t * dim)
    if tp > 1:
        vec = ctx.engine.allreduce(vec, ctx.tp_axis)
    return vec


def dlrm_forward(params, indices, ctx: ParCtx, use_pallas: bool = False,
                 *, dense=None, cfg: DLRMConfig | None = None):
    """indices: (B_local, T) -> (B_local, out_dim) click-through logits.
    FC layer i runs under the device scope `dlrm.fc<i>`. A DCNv2 `cfg`
    takes the DCNv2 step (`dcnv2_forward`, which needs `dense`)."""
    if cfg is not None and cfg.interaction == "dcnv2":
        if use_pallas:
            raise ValueError("the Pallas gather reads (T, V, D) stacks "
                             "row-minor; DCNv2's packed table is gathered "
                             "by XLA")
        return dcnv2_forward(params, indices, dense, ctx, cfg)
    vec = embedding_lookup(params["tables"], indices, ctx, use_pallas)
    tp = ctx.tp
    x = vec
    for i, fc in enumerate(params["fc"]):
        w, bias = fc["w"], fc["b"]
        with jax.named_scope(f"dlrm.fc{i}"):
            if i == 0 and tp > 1:
                # checkerboard FC1: row-partitioned input slice x column
                # slice
                in_l = w.shape[0]
                x_slice = jax.lax.dynamic_slice_in_dim(
                    x, ctx.tp_rank() * in_l, in_l, 1)
                if ctx.pcfg.collective_matmul:
                    y = ctx.engine.matmul_reduce_scatter(x_slice, w,
                                                         ctx.tp_axis)
                    y = ctx.engine.allgather(y, ctx.tp_axis).reshape(
                        x.shape[0], -1)
                else:
                    y = jnp.einsum("bi,io->bo", x_slice, w)
                    y = ctx.engine.allreduce(y, ctx.tp_axis)
            else:
                y = jnp.einsum("bi,io->bo", x, w)
                if tp > 1 and 0 < i < len(params["fc"]) - 1:
                    # column-parallel: out-dim sharded; gather for next
                    # layer
                    y = ctx.engine.allgather(
                        y.T, ctx.tp_axis).reshape(-1, x.shape[0]).T
            y = y + bias
            x = jax.nn.relu(y) if i < len(params["fc"]) - 1 else y
    return x


def bag_partials(table, indices, cfg: DLRMConfig, tp: int, rank):
    """One row shard's part of every bag. table: the shard's
    (rows_local, dim) block of the packed tables (`table_layout`);
    indices: (B, ids_per_query) global row ids, each table's bag side by
    side in table order; rank: the shard (an int or traced). Returns
    (B, n_tables * dim): per sample, each table's sum of the rows of its
    bag that this shard holds. The tp shards' partials add up to the
    pooled bags. Every id is gathered (ids the shard does not hold read
    its row 0) and masked: a static shape, whatever the ids."""
    per, off, _ = table_layout(cfg, tp)
    hots = cfg.hots
    slot_per = np.repeat(np.asarray(per, np.int32), hots)
    slot_off = np.repeat(np.asarray(off, np.int32), hots)
    local = indices - rank * slot_per
    hit = (local >= 0) & (local < slot_per)
    safe = jnp.where(hit, local + slot_off, 0)
    rows = jnp.take(table, safe, axis=0, mode="clip")
    rows = jnp.where(hit[..., None], rows, 0.0)         # (B, ids, dim)
    starts = itertools.accumulate((0,) + hots[:-1])
    return jnp.concatenate([rows[:, s:s + m].sum(axis=1)
                            for s, m in zip(starts, hots)], axis=1)


def dcnv2_forward(params, indices, dense, ctx: ParCtx, cfg: DLRMConfig):
    """The DLRM-DCNv2 serving step on one chip of the 'model' axis.
    indices: (B, ids_per_query) global ids, replicated; dense:
    (B / tp, dense_features), this chip's samples. Returns (B / tp,
    out_dim) logits of this chip's samples.

    Device scopes: `dlrm.bag` (the partial bags and the engine's
    reduce-scatter, nested as `engine.reduce_scatter`), `dlrm.bottom<i>`,
    `dlrm.cross<i>` (cross0 also forms x0) and `dlrm.top<i>`. Counters in
    the engine's registry, set as the step is traced: ids per query,
    table rows held per chip, and the reduce-scatter's input bytes per
    batch on each chip (0 where tp = 1 and no collective runs)."""
    tp = ctx.tp
    b = indices.shape[0]
    _, _, rows_l = table_layout(cfg, tp)
    bag_bytes = b * cfg.n_tables * cfg.emb_dim * \
        params["tables"].dtype.itemsize
    reg = ctx.engine.metrics
    reg.counter("dlrm.ids_per_query", cfg.ids_per_query)
    reg.counter("dlrm.table_rows_per_chip", rows_l)
    reg.counter("dlrm.bag_collective_bytes", bag_bytes if tp > 1 else 0)
    with jax.named_scope("dlrm.bag"):
        part = bag_partials(params["tables"], indices, cfg, tp,
                            ctx.tp_rank())
        pooled = ctx.engine.reduce_scatter(part, ctx.tp_axis).reshape(
            b // tp, -1)
    x = dense
    for i, layer in enumerate(params["bottom"]):
        with jax.named_scope(f"dlrm.bottom{i}"):
            x = jax.nn.relu(x @ layer["w"] + layer["b"])
    for i, layer in enumerate(params["cross"]):
        with jax.named_scope(f"dlrm.cross{i}"):
            if i == 0:
                x0 = xl = jnp.concatenate([x, pooled], axis=1)
            xl = x0 * ((xl @ layer["v"]) @ layer["w"] + layer["b"]) + xl
    x = xl
    n = len(params["fc"])
    for i, layer in enumerate(params["fc"]):
        with jax.named_scope(f"dlrm.top{i}"):
            x = x @ layer["w"] + layer["b"]
            if i < n - 1:
                x = jax.nn.relu(x)
    return x


def unpack_tables(tables, cfg: DLRMConfig, tp: int) -> list:
    """The packed (tp * rows_local, dim) tables -> the n_tables whole
    tables (rows_t, dim), padding rows dropped (tests, references)."""
    per, off, rows_l = table_layout(cfg, tp)
    out = []
    for t, rows in enumerate(cfg.rows):
        parts = [tables[s * rows_l + off[t]:s * rows_l + off[t] + per[t]]
                 for s in range(tp)]
        out.append(jnp.concatenate(parts)[:rows])
    return out


def dlrm_dcnv2_reference(tables, params, indices, dense, cfg: DLRMConfig):
    """The DLRM-DCNv2 forward in plain jax.numpy float32 at `highest`, on
    whole tables (`unpack_tables`), with no sharding and no engine.
    tables: n_tables arrays (rows_t, dim); params: the dense leaves
    (`bottom`, `cross`, `fc`); indices (B, ids_per_query); dense (B,
    dense_features) -> (B, out_dim). Bags are sum-pooled; x0 is the
    bottom output then the bags in table order; cross layer l is x0 *
    (W_l (V_l x_l) + b_l) + x_l; ReLU after every bottom layer and every
    top layer but the last."""
    with jax.default_matmul_precision("highest"):
        starts = itertools.accumulate((0,) + cfg.hots[:-1])
        bags = [tab[indices[:, s:s + m]].sum(axis=1)
                for tab, s, m in zip(tables, starts, cfg.hots)]
        x = dense
        for layer in params["bottom"]:
            x = jax.nn.relu(x @ layer["w"] + layer["b"])
        x0 = xl = jnp.concatenate([x] + bags, axis=1)
        for layer in params["cross"]:
            xl = x0 * ((xl @ layer["v"]) @ layer["w"] + layer["b"]) + xl
        return dlrm_mlp_reference(params["fc"], xl)


def dlrm_reference(params_full, indices):
    """Single-device oracle on gathered params (tests)."""
    tables = params_full["tables"]
    rows = tables[jnp.arange(tables.shape[0])[None, :], indices]  # (B,T,d)
    return dlrm_mlp_reference(params_full["fc"],
                              rows.reshape(indices.shape[0], -1))


def dlrm_mlp_reference(fc_params, x):
    """The FC stack on already looked-up concat vectors (B, T*dim): the
    oracle for tables that fit on no single device."""
    n = len(fc_params)
    for i, fc in enumerate(fc_params):
        x = x @ fc["w"] + fc["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x
