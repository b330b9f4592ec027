"""DLRM embedding lookup — scalar-prefetch DMA gather from HBM-resident tables.

The paper's DLRM embedding layers are "memory-bound ... accessed via
indexes, resulting in multiple random memory accesses" (§6). FPGA solutions
spread tables over HBM channels for parallel access; the TPU analogue is a
Pallas kernel whose DMAs are driven by the prefetched indices, so the
sparse access pattern never materializes a one-hot or a full-table read.

Layout: a (T, V, D) fp32 table stack with a small D (32 in the paper) is
held by the TPU row-minor — physically (T, D, V), tiled (8, 128) with V on
the lanes — because a D-minor layout would pad every row to 128 lanes. The
kernel reads that layout as it lies: it takes `swapaxes(tables, 1, 2)`
(a bitcast, no copy) in HBM and, per index, DMAs the lane-aligned (D, 128)
window holding the row, then picks the row's lane in VMEM. Its output is
(T, D, B), whose swap back to (T, B, D) is again a bitcast. V must be a
multiple of 128 (ops.py pads small tables; DLRM allocates aligned ones).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_B = 128     # lookups per grid step (the output block's lane width)


def _kernel(idx_ref, table_ref, o_ref, win_ref, sem):
    t = pl.program_id(0)
    n = o_ref.shape[2]
    base = pl.program_id(1) * n
    d = win_ref.shape[1]

    def start(r, carry):
        row = idx_ref[t, base + r]
        lo = pl.multiple_of((row // LANES) * LANES, LANES)
        pltpu.make_async_copy(table_ref.at[t, :, pl.ds(lo, LANES)],
                              win_ref.at[r], sem).start()
        return carry

    def wait(r, carry):
        # every copy moves the same (D, 128) bytes on one semaphore
        pltpu.make_async_copy(table_ref.at[0, :, pl.ds(0, LANES)],
                              win_ref.at[0], sem).wait()
        return carry

    lax.fori_loop(0, n, start, 0)
    lax.fori_loop(0, n, wait, 0)
    lane = lax.broadcasted_iota(jnp.int32, (d, LANES), 1)
    col = lax.broadcasted_iota(jnp.int32, (d, n), 1)

    def pick(r, acc):
        c = idx_ref[t, base + r] % LANES
        row = jnp.sum(jnp.where(lane == c, win_ref[r], 0), axis=1,
                      keepdims=True)                      # (D, 1)
        return jnp.where(col == r, row, acc)

    o_ref[0] = lax.fori_loop(0, n, pick, jnp.zeros((d, n), o_ref.dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(table_t, indices, *, interpret: bool):
    """table_t: (T, D, V) row-minor view of a (T, V, D) stack;
    indices: (T, B) int32 row ids -> (T, D, B) gathered rows.

    B is at most BLOCK_B or a multiple of it; V a multiple of 128."""
    t, d, v = table_t.shape
    t2, b = indices.shape
    assert t == t2 and v % LANES == 0, (table_t.shape, indices.shape)
    nb = min(b, BLOCK_B)
    assert b % nb == 0, (b, nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, b // nb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, d, nb), lambda i, j, idx: (i, 0, j)),
        scratch_shapes=[pltpu.VMEM((nb, d, LANES), table_t.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((t, d, b), table_t.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(indices.astype(jnp.int32), table_t)
