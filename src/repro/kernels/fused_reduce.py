"""Binary streaming plugin kernel: fused combine(+cast) in one VMEM pass.

ACCL+'s arithmetic plugin sits in the collective datapath and combines the
arriving network stream with the local operand at line rate. The TPU
analogue: when a ring-step chunk lands in HBM, the combine (add/max/...)
plus any dtype cast should be one fused VMEM-resident pass — two HBM reads,
one HBM write, no intermediate materialization.

Target: TPU VPU (8x128 lanes). Tiles are (block_rows, 128)-aligned; the
last axis must be a multiple of 128 (ops.py pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VPU-native tile: 8 sublanes x 128 lanes; block_rows rows of 128 lanes.
DEFAULT_BLOCK_ROWS = 256
LANES = 128

_COMBINE = {
    "add": lambda a, b: a + b,
    "max": jnp.maximum,
    "min": jnp.minimum,
    "mul": jnp.multiply,
}


def _kernel(x_ref, y_ref, o_ref, *, op: str, acc_dtype):
    x = x_ref[...].astype(acc_dtype)
    y = y_ref[...].astype(acc_dtype)
    o_ref[...] = _COMBINE[op](x, y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("op", "out_dtype", "block_rows",
                                             "interpret"))
def fused_combine(x, y, *, op: str = "add", out_dtype=None,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool):
    """Elementwise combine of two (rows, 128k)-shaped arrays.

    Accumulates in fp32 regardless of input dtype (the plugin's cast), then
    casts to `out_dtype` (default: x.dtype) on the way out.
    """
    assert x.shape == y.shape and x.ndim == 2, (x.shape, y.shape)
    rows, cols = x.shape
    assert cols % LANES == 0, f"cols {cols} must be 128-aligned (ops.py pads)"
    assert rows % block_rows == 0, f"rows {rows} % {block_rows}"
    out_dtype = out_dtype or x.dtype
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, op=op, acc_dtype=jnp.float32),
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(x, y)
