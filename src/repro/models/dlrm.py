"""Distributed DLRM inference — the paper's §6 use case, TPU-native.

Paper design (Fig. 15): embedding tables distributed over nodes 1-4,
FC1 checkerboard-decomposed over 8 nodes, FC2/FC3 pipelined on nodes 9/10,
all communication through ACCL+ streaming collectives.

TPU mapping over the (data, model) mesh:
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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.dlrm import DLRMConfig
from repro.core import telemetry
from repro.models.common import Builder, sharded_initializer
from repro.parallel.ops import ParCtx

# Rows per shard are a multiple of this, so the Pallas lookup reads every
# shard in place (kernels/embedding_gather.py: V on the 128 lanes).
ROW_ALIGN = 128


def dlrm_params(b: Builder, cfg: DLRMConfig, tp: int):
    """Tables stacked (T, rows, dim) sharded over model on rows; rows is
    rows_per_table rounded up to ROW_ALIGN per shard (ids stay below
    rows_per_table, so the tail rows are never looked up)."""
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
    """tables: (T, rows_local, dim) local slice over 'model'; indices:
    (B, T) global row ids. Returns (B, T*dim) concat vector, replicated.

    Each rank serves the rows it owns (partial vectors), then one engine
    allreduce assembles the concat vector — the paper's partial-embedding
    transmission from memory nodes to compute nodes. Runs under the device
    scope `dlrm.lookup` (the allreduce nests under it as `engine.*`).
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


def dlrm_forward(params, indices, ctx: ParCtx, use_pallas: bool = False):
    """indices: (B_local, T) -> (B_local, out_dim) click-through logits.
    FC layer i runs under the device scope `dlrm.fc<i>`."""
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
