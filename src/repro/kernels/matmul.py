"""Tiled MXU matmul — the DLRM FC / collective-matmul compute step.

The paper's DLRM FC layers are the compute hot-spot it distributes
(checkerboard decomposition, §6.1); each rank's local shard product is
exactly this kernel. It is also the per-step compute of the streaming
collective matmul (engine.allgather_matmul / matmul_reduce_scatter).

MXU mapping: (bm, bk) x (bk, bn) tiles, all multiples of 128, fp32
accumulator held in a VMEM scratch across the K grid dimension (innermost),
cast on the final K step. Grid order (m, n, k) keeps the accumulator live
for exactly one (m, n) tile at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256


def _kernel(x_ref, y_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def matmul_tiled(x, y, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                 bk: int = DEFAULT_BK, out_dtype=None,
                 interpret: bool):
    """x: (M, K), y: (K, N); M % bm == K % bk == N % bn == 0 (ops.py pads)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    out_dtype = out_dtype or x.dtype
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, y)
