"""The plain float64 reference of DLRM-DCNv2 (MLPerf, Criteo 1TB
multi-hot) the benchmark checks the timed path against, and the data it
defines.

Nothing here imports the program under test or takes anything it made.
Every table row and weight is a pure function of the seed
(`bench.weights`): table t's row r is `to_uniform(hash(key, (t, r, d)))`
over d, whatever the program's storage; the device materialises the
program's packed tables from that and this module recomputes the rows a
check needs. Dense weights use the scales of `bench.reference` (powers of
two, so host and device hold the same bits).

The forward pass (MLPerf's `torchrec_dlrm` DLRM_DCN): sum-pooled bags;
the bottom MLP with ReLU after every layer; x0 = [bottom output, bag 0,
..., bag T-1]; cross layer l: x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l
(V without bias); the top MLP with ReLU after every layer but the last.
"""
from __future__ import annotations

import numpy as np

from bench import weights
from bench.reference import BIAS_SCALE, TABLE_SCALE, TABLE_STREAM, fc_scale


def mlp_dims(cfg: dict) -> tuple[list, list]:
    """(bottom MLP widths, top MLP widths), input first."""
    d0 = (cfg["n_tables"] + 1) * cfg["emb_dim"]
    return ([cfg["dense_features"]] + list(cfg["bottom_dims"]),
            [d0] + list(cfg["fc_dims"]) + [cfg["out_dim"]])


def streams(cfg: dict) -> dict:
    """Stream ids of every dense weight after the tables' (0): bottom
    (w, b) per layer, cross (v, w, b) per layer, top (w, b) per layer."""
    bottom, top = mlp_dims(cfg)
    ids = iter(range(TABLE_STREAM + 1, 1 << 16))
    return {"bottom": [(next(ids), next(ids)) for _ in bottom[1:]],
            "cross": [(next(ids), next(ids), next(ids))
                      for _ in range(cfg["dcn_layers"])],
            "top": [(next(ids), next(ids)) for _ in top[1:]]}


def keys(seed: int, cfg: dict) -> np.ndarray:
    """The key of every stream, indexed by stream id."""
    n = 1 + sum(len(s) for layers in streams(cfg).values() for s in layers)
    return np.array([weights.key_of(seed, s) for s in range(n)], np.uint32)


def dense_shapes(cfg: dict) -> dict:
    """(shape, scale) of every dense weight, as `streams` lists them."""
    bottom, top = mlp_dims(cfg)
    d0, rank = top[0], cfg["dcn_rank"]

    def mlp(dims):
        return [(((a, b), fc_scale(a)), ((b,), BIAS_SCALE))
                for a, b in zip(dims[:-1], dims[1:])]
    return {"bottom": mlp(bottom),
            "cross": [(((d0, rank), fc_scale(d0)),
                       ((rank, d0), fc_scale(rank)),
                       ((d0,), BIAS_SCALE))] * cfg["dcn_layers"],
            "top": mlp(top)}


def dense_np(key_arr, cfg: dict) -> dict:
    """Every dense weight, float32 on the host, shaped as `dense_shapes`."""
    out = {}
    for group, layers in dense_shapes(cfg).items():
        out[group] = [
            tuple(weights.uniform_np(key_arr[s], shape, scale)
                  for s, (shape, scale) in zip(ids, layer))
            for ids, layer in zip(streams(cfg)[group], layers)]
    return out


def slot_tables(cfg: dict) -> np.ndarray:
    """The table of each id slot of a query: bags side by side."""
    return np.repeat(np.arange(cfg["n_tables"]), cfg["multi_hot"])


def table_rows_np(key, tables: np.ndarray, rows: np.ndarray,
                  dim: int) -> np.ndarray:
    """Row `rows[i]` of table `tables[i]` (broadcast), float32: shape
    rows.shape + (dim,)."""
    t = np.broadcast_to(tables, rows.shape).astype(np.uint32)[..., None]
    r = rows.astype(np.uint32)[..., None]
    d = np.arange(dim, dtype=np.uint32)
    with np.errstate(over="ignore"):
        h = weights.hash_index(np.uint32(key), (t, r, d), np)
        return weights.to_uniform(h, TABLE_SCALE, np)


def logits(key_arr, dense_w: dict, cfg: dict, ids: np.ndarray,
           dense: np.ndarray) -> np.ndarray:
    """float64 forward for ids (Q, ids per query) and dense (Q, features)
    -> (Q, out_dim)."""
    # each distinct (table, row) made once: hot rows repeat under Zipf
    pair = (np.broadcast_to(slot_tables(cfg), ids.shape).astype(np.int64)
            << 32) | ids.astype(np.int64)
    uniq, inv = np.unique(pair.ravel(), return_inverse=True)
    rows = table_rows_np(key_arr[TABLE_STREAM], uniq >> 32,
                         uniq & 0xFFFFFFFF, cfg["emb_dim"])
    rows = rows.astype(np.float64)[inv].reshape(ids.shape + (-1,))
    starts = np.cumsum([0] + list(cfg["multi_hot"][:-1]))
    bags = [rows[:, s:s + m].sum(axis=1)
            for s, m in zip(starts, cfg["multi_hot"])]

    def f64(*a):
        return [x.astype(np.float64) for x in a]

    x = dense.astype(np.float64)
    for w, b in (f64(*layer) for layer in dense_w["bottom"]):
        x = np.maximum(x @ w + b, 0.0)
    x0 = xl = np.concatenate([x] + bags, axis=1)
    for v, w, b in (f64(*layer) for layer in dense_w["cross"]):
        xl = x0 * ((xl @ v) @ w + b) + xl
    x = xl
    top = dense_w["top"]
    for i, (w, b) in enumerate(f64(*layer) for layer in top):
        x = x @ w + b
        if i < len(top) - 1:
            x = np.maximum(x, 0.0)
    return x
