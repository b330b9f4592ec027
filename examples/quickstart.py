"""Quickstart: the collective engine's two APIs on a simulated cluster.

Runs on 8 virtual CPU devices — the ACCL+ simulation-platform analogue.

  JAX_PLATFORMS=cpu python examples/quickstart.py
"""
import os

if os.environ.get("JAX_PLATFORMS") == "cpu":  # 8 virtual host devices
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch import configure_compile_cache
from repro.core import CollectiveEngine, Communicator, Selector
from repro.core.topology import make_mesh


def main():
    configure_compile_cache()
    mesh = make_mesh((8,), ("x",))
    engine = CollectiveEngine(mesh, backend="microcode")

    # ---- MPI-like API (paper Listing 1): buffers in, buffers out ----------
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8 * 1024,)),
                    jnp.float32)

    def program(shard):
        total = engine.allreduce(shard, "x", algorithm="ring")
        biggest = engine.allreduce(shard, "x", op="max",
                                   algorithm="recursive_doubling")
        root_view = engine.gather(shard, "x", root=0,
                                  algorithm="binomial_tree")
        return total[:4], biggest[:4], root_view[:4]

    g = engine.run(program, in_specs=P("x"), out_specs=P(None))
    total, biggest, root_view = g(x)
    print("allreduce[:4]      ", np.asarray(total))
    print("max-reduce[:4]     ", np.asarray(biggest))
    print("gather@root[:4]    ", np.asarray(root_view))

    # ---- Streaming API (paper Listing 2): compute fused with comm ---------
    rows = jnp.asarray(np.random.default_rng(1).normal(size=(8 * 32, 16)),
                       jnp.float32)          # row-sharded activations
    w = jnp.asarray(np.random.default_rng(2).normal(size=(16, 64)),
                    jnp.float32)

    def streaming(shard, w):
        # each ring step multiplies a shard while the next is on the wire
        return engine.allgather_matmul(shard, w, "x")

    g2 = engine.run(streaming, in_specs=(P("x"), P()), out_specs=P(None))
    y = g2(rows, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(rows) @ w,
                               atol=1e-4)
    print("streaming collective matmul:", y.shape, "(matches rows @ w)")

    # ---- Runtime algorithm selection (the paper's firmware tuning) --------
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    for size in (1 << 10, 1 << 17, 1 << 24):
        c = sel.choose("allreduce", size, comm)
        print(f"selector: allreduce {size >> 10:6d}KB -> "
              f"{c.algorithm:18s}/{c.protocol:10s} "
              f"predicted {c.predicted_s * 1e6:8.1f}us on TPU ICI")
    # pin an algorithm at runtime, no code/recompile of the model needed
    sel.set_tuning("allreduce", "bidi_ring", lo_bytes=1 << 20)
    c = sel.choose("allreduce", 1 << 24, comm)
    print("after set_tuning:", c.algorithm)


if __name__ == "__main__":
    main()
