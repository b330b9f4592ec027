"""Paper use case 1 (Fig. 16) as the OFFLOAD demo: distributed
vector-matrix multiply with the weight matrix row-partitioned across
ranks and the partial products combined by engine `reduce` requests —
issued NON-BLOCKING into the CCLO-style request queue.

The offload pattern (the paper's second headline role): the caller tiles
the output, computes tile t+1 on the MXU while tile t's partial
reduction drains from the queue, and only materializes results at the
end. `Sequencer.makespan` prices the drained queue — independent tile
reductions overlap their per-hop latency on the shared link — against
the serial sum of blocking `Program.cost`s.

  JAX_PLATFORMS=cpu python examples/distributed_vecmat.py
"""
import os

if os.environ.get("JAX_PLATFORMS") == "cpu":  # 8 virtual host devices
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch import configure_compile_cache  # noqa: E402
from repro.core import CollectiveEngine, Communicator  # noqa: E402
from repro.core.hw_spec import ACCL_CLUSTER  # noqa: E402
from repro.core.topology import make_mesh  # noqa: E402

TILES = 4  # output tiles in flight: tile t+1 computes while t drains


def main():
    configure_compile_cache()
    mesh = make_mesh((8,), ("x",))
    engine = CollectiveEngine(mesh, backend="microcode")
    rng = np.random.default_rng(0)

    # NOTE: the 8 "devices" share one physical core here, so measured
    # speedup cannot exceed 1; the model columns are the paper-cluster
    # prediction (compute / 8 + the reduction: serial-blocking vs the
    # queue's makespan).
    print("size,single_us,dist_us,measured_x,model_blocking_x,"
          "model_offload_x,overlap_x")
    for size in (512, 1024, 2048, 4096):
        w = jnp.asarray(rng.normal(size=(size, size)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(size,)), jnp.float32)

        single = jax.jit(lambda a, b: a @ b)
        single(x, w).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            y_ref = single(x, w)
        y_ref.block_until_ready()
        us_single = (time.perf_counter() - t0) / 20 * 1e6

        # rank r holds rows chunk r of W and the matching slice of x.
        # Each output tile's partial product is ISSUED as a non-blocking
        # reduce; the next tile's matmul runs while it drains.
        tile = size // TILES

        def dist(xs, ws):
            reqs = []
            for t in range(TILES):
                partial = xs @ ws[:, t * tile:(t + 1) * tile]
                reqs.append(engine.ireduce(partial, "x",
                                           algorithm="binomial_tree"))
            # materialize: FIFO drain of the outstanding tile reductions
            return jnp.concatenate([r.wait() for r in reqs])

        g = jax.jit(jax.shard_map(dist, mesh=mesh,
                                  in_specs=(P("x"), P("x", None)),
                                  out_specs=P(), check_vma=False))
        y = g(x, w)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(20):
            y = g(x, w)
        jax.block_until_ready(y)
        us_dist = (time.perf_counter() - t0) / 20 * 1e6

        err = float(jnp.abs(y - y_ref).max())
        assert err < 1e-2, err

        # queue-level model on the paper cluster: price the SAME request
        # pattern (one binomial-tree reduce per tile) via the sequencer,
        # without executing anything
        accl_comm = Communicator(axis="x", size=8, hw=ACCL_CLUSTER)
        seq = engine.queue
        for t in range(TILES):
            seq.issue("reduce", np.zeros((tile,), np.float32), "x",
                      algorithm="binomial_tree")
        t_queue = seq.makespan("x", comm=accl_comm)
        t_serial = seq.serial_cost("x", comm=accl_comm)
        seq.clear()  # model-only queue: drop without executing

        t_single = 2 * size * size / 50e9
        model_blocking = t_single / (t_single / 8 + t_serial)
        model_offload = t_single / (t_single / 8 + t_queue)
        print(f"{size},{us_single:.1f},{us_dist:.1f},"
              f"{us_single/us_dist:.2f},{model_blocking:.2f},"
              f"{model_offload:.2f},{t_serial/t_queue:.2f}")
        assert t_queue < t_serial, (
            "independent tile reductions must overlap in the makespan")


if __name__ == "__main__":
    main()
