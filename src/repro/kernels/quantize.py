"""Unary streaming plugin kernel: per-block int8 quantize / dequantize.

ACCL+'s unary plugins compress/encrypt in-flight data. Ours is the
compressed-gradient codec: symmetric per-block int8 with one fp32 scale per
QUANT_BLOCK elements (4x wire-byte reduction for fp32 gradients, matching
core/plugins.py wire format).

Layout: flat input reshaped to (n_blocks, QUANT_BLOCK); each Pallas grid
step quantizes up to BLOCK_ROWS blocks resident in VMEM. The scales are a
1-D fp32 array, which the TPU lays out in tiles of 1024 once it holds that
many; a scale block must match that tiling, so n_blocks is a multiple of
128 up to BLOCK_ROWS (one grid step) and a multiple of BLOCK_ROWS above it
(`quant_rows` gives the padded count).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QUANT_BLOCK = 256   # elements per scale (== plugins.QUANT_BLOCK)
BLOCK_ROWS = 1024   # quant blocks per grid step (the 1-D scale tiling)
MIN_ROWS = 128      # smallest scale tile


def quant_rows(n_blocks: int) -> int:
    """The block count a quantize call runs at: n_blocks rounded up to a
    multiple of MIN_ROWS, or of BLOCK_ROWS above BLOCK_ROWS."""
    mult = MIN_ROWS if n_blocks <= BLOCK_ROWS else BLOCK_ROWS
    return max(mult, -(-n_blocks // mult) * mult)


def _block_rows(rows: int) -> int:
    assert rows == quant_rows(rows), f"{rows} blocks (pad with quant_rows)"
    return min(rows, BLOCK_ROWS)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)               # (rows, QUANT_BLOCK)
    scale = jnp.max(jnp.abs(x), axis=1) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = q * s_ref[...][:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_blocks(x2d, *, interpret: bool):
    """(n_blocks, QUANT_BLOCK) fp -> (int8 payload, fp32 scales)."""
    rows, cols = x2d.shape
    assert cols == QUANT_BLOCK, (rows, cols)
    br = _block_rows(rows)
    return pl.pallas_call(
        _quant_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, cols), jnp.int8),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
        ),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((br, cols), lambda i: (i, 0)),
            pl.BlockSpec((br,), lambda i: (i,)),
        ),
        interpret=interpret,
    )(x2d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_blocks(q2d, scales, *, interpret: bool):
    """(n_blocks, QUANT_BLOCK) int8 + (n_blocks,) scales -> fp32."""
    rows, cols = q2d.shape
    assert cols == QUANT_BLOCK, (rows, cols)
    br = _block_rows(rows)
    return pl.pallas_call(
        _dequant_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, cols), lambda i: (i, 0)),
            pl.BlockSpec((br,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        interpret=interpret,
    )(q2d, scales)
