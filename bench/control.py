"""The controls: each cell's plain reference put in the program's place
at the nearest precision below the one its configuration states, to show
that the comparison deciding `correct` fails it.

- DLRM: the configuration states float32 arithmetic, `highest` on a
  TPU, so the control is the reference at `high`: every matmul in three
  bfloat16 passes (hi*hi + hi*lo + lo*hi of each operand's bfloat16
  split, accumulating in float32). The passes are written out, so the
  control is the same on every backend; XLA:TPU's own `high` reads
  further from float64 than they do.
- Collectives: the configuration states exact float32 results, so the
  control runs each chain in bfloat16.

`bench/calibrate.py` reads them on the chip; `bench/tests` keeps them at a
size a test run holds. The benchmark's own runs never run them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference


def _split(x):
    """x = hi + lo + (the rest), hi and lo bfloat16. `reduce_precision`
    rounds as a cast to bfloat16 does, where XLA, allowed excess
    precision, would drop a cast there and back and leave lo = 0."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def dot_high(a, b):
    """a @ b in three bfloat16 passes, float32 accumulation."""
    (ah, al), (bh, bl) = _split(a), _split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)
    return (dot(al, bh) + dot(ah, bl)) + dot(ah, bh)


def dlrm_forward_high(params, ids):
    """The DLRM reference on the global arrays, every matmul at `high`."""
    tables = params["tables"]
    rows = tables[jnp.arange(tables.shape[0])[None, :], ids]
    x = rows.reshape(ids.shape[0], -1)
    n = len(params["fc"])
    for i, layer in enumerate(params["fc"]):
        x = dot_high(x, layer["w"]) + layer["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def install_dlrm(drv) -> None:
    """Serve the cell's window with the reference at `high`."""
    drv.serve = jax.jit(dlrm_forward_high)


def install_coll(drv) -> None:
    """Run each program's chain in bfloat16 on the whole (n, m) array."""
    for p in drv.programs:
        def chain(x, name=p["name"]):
            y = x.astype(jnp.bfloat16)
            for _ in range(drv.steps):
                y = reference.coll_step(name, y, drv.root, jnp)
            return y.astype(jnp.float32)
        p["fn"] = jax.jit(chain)


def install(drv) -> None:
    (install_coll if hasattr(drv, "programs") else install_dlrm)(drv)
