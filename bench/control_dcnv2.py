#!/usr/bin/env python3
"""The control of the DLRM-DCNv2 cell: its reference put in the program's
place at the nearest precision below the one the configuration states, to
show that the comparison deciding `correct` fails it.

The configuration states float32 arithmetic (`highest` on a TPU), so the
control computes every matmul of the dense part (bottom MLP, cross
network, top MLP) at `high`: three bfloat16 passes written out
(`bench.control.dot_high`), the same on every backend. The bags hold no
matmul: each chip sums its rows of every bag as the program stores them
(`repro.models.dlrm.bag_partials`, exact float32 sums) and XLA's own
reduce-scatter hands each chip its samples' bags.

Read its reading beside the program's, on the chip, in one process:

  python3 bench/control_dcnv2.py --workload dlrm4.dcnv2-b2048-zipf \
      --seeds 12 --control-seeds 3 --seconds 2 --out <file.json>

(`bench/calibrate.py`'s run, with this control installed). The
benchmark's own runs never run it.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.control import dot_high  # noqa: E402


def install(drv) -> None:
    """Serve the cell's window with the dense part at `high`."""
    from repro.models import dlrm as dm
    cfg, tp = drv.mcfg, drv.chips

    def relu_layer(x, layer):
        return jax.nn.relu(dot_high(x, layer["w"]) + layer["b"])

    def step(params, ids, dense):
        rank = jax.lax.axis_index("model")
        part = dm.bag_partials(params["tables"], ids, cfg, tp, rank)
        pooled = jax.lax.psum_scatter(part, "model", scatter_dimension=0,
                                      tiled=True)
        x = dense
        for layer in params["bottom"]:
            x = relu_layer(x, layer)
        x0 = xl = jnp.concatenate([x, pooled], axis=1)
        for layer in params["cross"]:
            xl = x0 * (dot_high(dot_high(xl, layer["v"]), layer["w"])
                       + layer["b"]) + xl
        x = xl
        *hidden, last = params["fc"]
        for layer in hidden:
            x = relu_layer(x, layer)
        return dot_high(x, last["w"]) + last["b"]

    drv.serve = jax.jit(jax.shard_map(
        step, mesh=drv.mesh,
        in_specs=(drv.specs, P(None, None), P("model", None)),
        out_specs=P("model", None), check_vma=False))


if __name__ == "__main__":
    from bench import calibrate, control
    control.install = install      # what calibrate.py installs
    sys.exit(calibrate.main())
