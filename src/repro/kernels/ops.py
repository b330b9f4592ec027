"""Public jit'd wrappers around the Pallas kernels.

Handles shape padding/alignment so callers can pass arbitrary shapes, and
is the one place that chooses interpret mode: compiled Mosaic when JAX's
backend is the TPU, the interpreter otherwise (the CPU validation path).
A caller that must pin the mode (tests) passes `interpret=` explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import embedding_gather as _eg
from repro.kernels import fused_reduce as _fr
from repro.kernels import matmul as _mm
from repro.kernels import quantize as _qz

LANES = 128


@functools.cache
def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mode(interpret):
    return _interpret() if interpret is None else interpret


def _pad_dim(x, dim: int, mult: int):
    pad = (-x.shape[dim]) % mult
    if pad == 0:
        return x, x.shape[dim]
    widths = [(0, 0)] * x.ndim
    widths[dim] = (0, pad)
    return jnp.pad(x, widths), x.shape[dim]


def fused_add(x, y, out_dtype=None, interpret=None):
    """Streaming binary plugin: x + y (fp32 accumulate, fused cast)."""
    return fused_combine(x, y, "add", out_dtype, interpret)


def fused_combine(x, y, op: str = "add", out_dtype=None, interpret=None):
    shape = x.shape
    flat_x, n = _pad_dim(x.reshape(-1), 0, _fr.DEFAULT_BLOCK_ROWS * LANES)
    flat_y, _ = _pad_dim(y.reshape(-1), 0, _fr.DEFAULT_BLOCK_ROWS * LANES)
    out = _fr.fused_combine(flat_x.reshape(-1, LANES),
                            flat_y.reshape(-1, LANES), op=op,
                            out_dtype=out_dtype, interpret=_mode(interpret))
    return out.reshape(-1)[:n].reshape(shape)


def quantize_int8(flat, interpret=None):
    """flat (N,) fp -> (payload int8 (Np,), scales fp32 (Np/256,)).

    Np is N padded to `quantize.quant_rows` scale blocks; decompress
    slices back."""
    flat = flat.reshape(-1)
    rows = _qz.quant_rows(-(-flat.shape[0] // _qz.QUANT_BLOCK))
    flat, _ = _pad_dim(flat, 0, rows * _qz.QUANT_BLOCK)
    q, s = _qz.quantize_blocks(flat.reshape(-1, _qz.QUANT_BLOCK),
                               interpret=_mode(interpret))
    return q.reshape(-1), s


def dequantize_int8(payload, scales, interpret=None):
    out = _qz.dequantize_blocks(payload.reshape(-1, _qz.QUANT_BLOCK), scales,
                                interpret=_mode(interpret))
    return out.reshape(-1)


def matmul(x, y, out_dtype=None, bm=None, bn=None, bk=None, interpret=None):
    """General (M,K)@(K,N) with automatic 128-alignment padding."""
    m, k = x.shape
    _, n = y.shape
    bm = bm or min(_mm.DEFAULT_BM, _ceil_mult(m, LANES))
    bn = bn or min(_mm.DEFAULT_BN, _ceil_mult(n, LANES))
    bk = bk or min(_mm.DEFAULT_BK, _ceil_mult(k, LANES))
    xp, _ = _pad_dim(x, 0, bm)
    xp, _ = _pad_dim(xp, 1, bk)
    yp, _ = _pad_dim(y, 0, bk)
    yp, _ = _pad_dim(yp, 1, bn)
    out = _mm.matmul_tiled(xp, yp, bm=bm, bn=bn, bk=bk,
                           out_dtype=out_dtype, interpret=_mode(interpret))
    return out[:m, :n]


def _ceil_mult(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def embedding_gather(table, indices, interpret=None):
    """(V, D) table + (B,) ids -> (B, D), or a (T, V, D) stack + (T, B)
    ids -> (T, B, D).

    The kernel reads the table in place. Only a table whose V is not a
    multiple of 128 is padded (a copy): DLRM allocates aligned tables
    (`models.dlrm.dlrm_params`), so its lookups never take that branch.
    """
    if table.ndim == 2:
        return embedding_gather(table[None], indices[None], interpret)[0]
    table, _ = _pad_dim(table, 1, LANES)
    b = indices.shape[1]
    idx, _ = _pad_dim(indices.astype(jnp.int32), 1,
                      _eg.BLOCK_B if b > _eg.BLOCK_B else 1)
    out = _eg.gather_rows(jnp.swapaxes(table, 1, 2), idx,
                          interpret=_mode(interpret))
    return jnp.swapaxes(out, 1, 2)[:, :b]
