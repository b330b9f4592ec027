"""A new collective WITHOUT touching the engine — ACCL+'s core promise.

In ACCL+ (§4.2) collectives are software-defined microprograms over a
fixed set of DMA/packetizer primitives, so a new collective is new uC
firmware — no circuit re-synthesis. This repo reproduces that contract:
a collective is a `Schedule` (pure data + rank closures); the engine
compiles it to the micro-op IR and executes it through the same
`execute_program` data plane as every built-in.

This example registers `scatter` — MPI_Scatter, which the built-in table
does not provide — entirely out of tree, with two algorithms:

  linear         root sends chunk j straight to rank j (n-1 steps)
  binomial_tree  recursive halving of the root's range (log2 n steps)

and shows the full stack working on it: selector pricing + auto choice,
numpy-simulator validation against an oracle, and segmented execution.

  JAX_PLATFORMS=cpu python examples/custom_collective.py
"""
import os

if os.environ.get("JAX_PLATFORMS") == "cpu":  # 8 virtual host devices
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch import configure_compile_cache
from repro.core import (
    CollectiveEngine, Communicator, Schedule, Sel, Step,
    register_collective, simulator,
)
from repro.core.topology import make_mesh


# --------------------------------------------------------------------------
# The "firmware": two scatter schedules, written like any in-tree generator
# --------------------------------------------------------------------------

def linear_scatter(comm: Communicator, root: int = 0) -> Schedule:
    """Root sends chunk j of its buffer straight to rank j (n-1 steps).

    relay='original': every step wires the root's untouched input. Each
    non-root rank receives exactly once (mask_recv keeps the others')."""
    n = comm.size
    steps = tuple(
        Step(perm=((root, (root + i + 1) % n),), op="copy",
             send_sel=Sel.chunk(lambda r, s, i=i: (root + i + 1) % n),
             recv_sel=Sel.chunk(lambda r, s, i=i: (root + i + 1) % n),
             bytes_frac=1.0 / n, mask_recv=True)
        for i in range(n - 1)
    )
    return Schedule(
        name="linear", collective="scatter", nranks=n, steps=steps,
        chunks=n, result="shard", owned_chunk=lambda r: r,
        relay="original",
    )


def binomial_tree_scatter(comm: Communicator, root: int = 0) -> Schedule:
    """Each round halves the chunk range a holder forwards: log2(n) steps,
    moving (n/2 + n/4 + ...) chunks total — the rendezvous variant."""
    n = comm.size
    k = comm.log2_size
    if (1 << k) != n:
        raise ValueError("binomial_tree_scatter needs power-of-two ranks")
    steps = []
    for j in range(k):
        half = n >> (j + 1)  # chunks forwarded per pair this round
        pairs = tuple(
            ((root + m * 2 * half) % n, (root + m * 2 * half + half) % n)
            for m in range(1 << j)
        )

        def rng(r, s, half=half, root=root, n=n):
            # both ends of a pair name the receiver's range (rel | half)
            rel = (r - root) % n
            return ((rel | half), half)

        steps.append(Step(
            perm=pairs, op="copy",
            send_sel=Sel.range(rng), recv_sel=Sel.range(rng),
            bytes_frac=half / n, mask_recv=True,
        ))
    return Schedule(
        name="binomial_tree", collective="scatter", nranks=n,
        steps=tuple(steps), chunks=n, result="shard",
        owned_chunk=lambda r: r, relay="buffer",
    )


def main():
    configure_compile_cache()
    # -- register: this is ALL it takes to deploy a new collective ----------
    register_collective("scatter", linear_scatter, algorithm="linear",
                        protocols=("eager", "rendezvous"))
    register_collective("scatter", binomial_tree_scatter,
                        algorithm="binomial_tree",
                        protocols=("rendezvous",))

    # -- validate the microprogram in the numpy simulator first -------------
    n = 8
    comm = Communicator(axis="x", size=n)
    rng = np.random.default_rng(0)
    full = rng.normal(size=(n * 4,)).astype(np.float32)
    inputs = [full.copy() if r == 0 else np.zeros_like(full)
              for r in range(n)]
    for gen in (linear_scatter, binomial_tree_scatter):
        outs = simulator.simulate(gen(comm), inputs)
        for r in range(n):
            np.testing.assert_allclose(outs[r][r * 4:(r + 1) * 4],
                                       full[r * 4:(r + 1) * 4])
        print(f"simulator: {gen.__name__} == oracle on {n} ranks")

    # -- the selector prices it next to nothing else ------------------------
    eng = CollectiveEngine(make_mesh((n,), ("x",)), backend="microcode")
    for size in (1 << 10, 1 << 22):
        c = eng.selector.choose("scatter", size, comm)
        print(f"selector: scatter {size >> 10:5d}KB -> "
              f"{c.algorithm:14s}/{c.protocol:10s} "
              f"segments={c.segments} "
              f"predicted {c.predicted_s * 1e6:7.1f}us")

    # -- and the engine runs it through the same execute_program path -------
    def program(shard):
        # every rank contributes its shard; only root's buffer matters
        return eng.collective("scatter", shard, "x", algorithm="auto")

    g = eng.run(program, in_specs=P("x"), out_specs=P("x"))
    data = rng.normal(size=(n, 16)).astype(np.float32)
    out = np.asarray(g(jax.numpy.asarray(data)))
    # rank r's returned shard is chunk r of rank-0's (the root's) input
    csize = data[0].size // n
    for r in range(n):
        np.testing.assert_allclose(
            out[r * (16 // n):(r + 1) * (16 // n)].reshape(-1)[:csize],
            data[0].reshape(-1)[r * csize:(r + 1) * csize], atol=1e-6)
    print("engine:   scatter(auto) through execute_program matches root's "
          "chunks")

    # segmented execution works on it too — no extra code
    out_seg = np.asarray(eng.run(
        lambda s: eng.collective("scatter", s, "x", algorithm="linear",
                                 segments=4),
        in_specs=P("x"), out_specs=P("x"))(jax.numpy.asarray(data)))
    base = np.asarray(eng.run(
        lambda s: eng.collective("scatter", s, "x", algorithm="linear",
                                 segments=1),
        in_specs=P("x"), out_specs=P("x"))(jax.numpy.asarray(data)))
    np.testing.assert_array_equal(out_seg, base)
    print("engine:   segmented scatter bitwise-equal to unsegmented")


if __name__ == "__main__":
    main()
