"""Paper use case 2 (Fig. 17): distributed DLRM inference serving.

Embedding tables shard over the model axis (the HBM-capacity argument),
FC1 is checkerboard-decomposed, partial embedding vectors and FC1 partial
products travel through the collective engine. Serves batched requests and
reports latency/throughput vs the single-device baseline.

  python examples/dlrm_serve.py --batches 20

The model axis spans every device JAX sees unless --devices says fewer
(with JAX_PLATFORMS=cpu: 8 virtual host devices).
"""
import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import argparse  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.base import ParallelConfig  # noqa: E402
from repro.configs.dlrm import DLRMConfig  # noqa: E402
from repro.core import CollectiveEngine  # noqa: E402
from repro.core.topology import make_mesh  # noqa: E402
from repro.launch import configure_compile_cache  # noqa: E402
from repro.models import dlrm as dlrm_mod  # noqa: E402
from repro.parallel.ops import ParCtx  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--tables", type=int, default=32)
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--devices", type=int, default=None)
    args = ap.parse_args()
    configure_compile_cache()

    n = args.devices or jax.device_count()
    cfg = DLRMConfig(n_tables=args.tables, emb_dim=32,
                     rows_per_table=args.rows, fc_dims=(2048, 512, 256))
    mesh = make_mesh((1, 1, n), ("pod", "data", "model"))
    engine = CollectiveEngine(mesh, backend="microcode")
    ctx = ParCtx(engine=engine, pcfg=ParallelConfig(), mesh=mesh)

    params = dlrm_mod.dlrm_init(cfg, mesh, seed=0)
    specs = dlrm_mod.dlrm_specs(cfg, n)
    emb_gb = args.tables * args.rows * 32 * 4 / 2**30
    print(f"tables: {args.tables} x {args.rows} rows "
          f"({emb_gb:.2f} GiB embeddings, sharded {n}-way)")

    serve = jax.jit(jax.shard_map(
        lambda p, i: dlrm_mod.dlrm_forward(p, i, ctx),
        mesh=mesh, in_specs=(specs, P(None, None)),
        out_specs=P(None, None), check_vma=False))
    ref = jax.jit(dlrm_mod.dlrm_reference)

    rng = np.random.default_rng(0)
    reqs = [jnp.asarray(rng.integers(0, args.rows,
                                     (args.batch_size, args.tables)),
                        jnp.int32) for _ in range(args.batches)]
    # warmup + correctness
    out = serve(params, reqs[0])
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref(params, reqs[0])),
                               atol=1e-2, rtol=1e-2)

    for name, fn in (("distributed", lambda r: serve(params, r)),
                     ("single_node", lambda r: ref(params, r))):
        fn(reqs[0]).block_until_ready()
        t0 = time.perf_counter()
        for r in reqs:
            out = fn(r)
        out.block_until_ready()
        dt = time.perf_counter() - t0
        lat = dt / args.batches * 1e3
        tput = args.batches * args.batch_size / dt
        print(f"{name:12s} latency {lat:7.2f} ms/batch   "
              f"throughput {tput:9.0f} q/s")


if __name__ == "__main__":
    main()
