"""Plain references the benchmark checks the timed path against, and the
data they define. Nothing here imports the program under test or takes
anything it made: the DLRM's tables and weights are pure functions of
the seed (`bench.weights`), which the device materialises for the
program and this module recomputes for the rows it needs.
"""
from __future__ import annotations

import numpy as np

from bench import weights

# DLRM data: stream ids and scales. Scales are powers of two, so the
# device's float32 values and the host's are the same bits.
TABLE_STREAM, TABLE_SCALE, BIAS_SCALE = 0, 0.5, 2.0 ** -4


def fc_streams(i: int) -> tuple[int, int]:
    """(weight stream, bias stream) of FC layer i."""
    return 1 + 2 * i, 2 + 2 * i


def fc_scale(fan_in: int) -> float:
    """Uniform(-s, s) with s the power of two nearest sqrt(3 / fan_in):
    unit-variance inputs give unit-variance outputs."""
    return weights.pow2_scale((3.0 / fan_in) ** 0.5)


def dlrm_keys(seed: int, layers: int) -> np.ndarray:
    return np.array([weights.key_of(seed, s) for s in range(1 + 2 * layers)],
                    np.uint32)


def dlrm_fc_np(keys, dims) -> list:
    """The FC stack's float32 weights and biases, on the host."""
    out = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        ws, bs = fc_streams(i)
        out.append((weights.uniform_np(keys[ws], (a, b), fc_scale(a)),
                    weights.uniform_np(keys[bs], (b,), BIAS_SCALE)))
    return out


def dlrm_logits(keys, fc, ids: np.ndarray, emb_dim: int) -> np.ndarray:
    """float64 DLRM forward for ids (Q, T): look up each id's row (made
    from the seed), concatenate, run the FC stack with ReLU between."""
    q, t = ids.shape
    rows = weights.uniform_rows_np(keys[TABLE_STREAM], ids, emb_dim,
                                   TABLE_SCALE, lead=t)
    x = rows.reshape(q, t * emb_dim).astype(np.float64)
    for i, (w, b) in enumerate(fc):
        x = x @ w.astype(np.float64) + b.astype(np.float64)
        if i < len(fc) - 1:
            x = np.maximum(x, 0.0)
    return x


def logit_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest gap between served and reference logits, as a share of the
    largest reference logit: one number for every answer compared."""
    scale = float(np.abs(want).max())
    return float(np.abs(got.astype(np.float64) - want).max()) / scale


# --------------------------------------------------------------------------
# Collectives: a numpy oracle of each chain, exact in int64.
# --------------------------------------------------------------------------

def coll_step(name: str, x, root: int, xp=np):
    """One link of a chain: x (n ranks, m) -> the next input (n, m), as
    the benchmark's chain defines it from the collective's MPI result.
    Each link reads other ranks' data, so a missing exchange shows.
    `xp` is numpy (the oracle) or jax.numpy (the control)."""
    n, m = x.shape
    c = m // n
    r = np.arange(n)
    on_root = (r == root)[:, None]
    if name == "allreduce":
        return xp.broadcast_to(x.sum(0), (n, m))
    if name == "reduce_scatter":
        return xp.tile(x.sum(0).reshape(n, c), (1, n))
    if name == "allgather":
        return x[(r + 1) % n] + x
    if name == "bcast":
        return x[root][None] + x
    if name == "reduce":
        return xp.where(on_root, x.sum(0)[None], x)
    if name == "gather":
        return xp.where(on_root, (x[(root + 1) % n] + x[root])[None], x)
    if name == "alltoall":
        return x.reshape(n, n, c).transpose(1, 0, 2).reshape(n, m) + x
    raise ValueError(name)


def coll_chain(name: str, x: np.ndarray, root: int, steps: int) -> np.ndarray:
    y = x.astype(np.int64)
    for _ in range(steps):
        y = coll_step(name, y, root)
    return y
