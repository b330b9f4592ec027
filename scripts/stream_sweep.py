#!/usr/bin/env python3
"""Time each streamed and unsegmented form of the large collectives.

For every (collective, algorithm, segments) form listed in `FORMS`, one
jitted program issues the collective `--chain` times back to back at
`--bytes` per rank, with the same glue between links as the benchmark's
collective grid (`bench/drivers/coll_grid.py`), so a form's time per call
compares with that grid's. Each program is compiled and warmed first, then
timed twice:

  * `host_us`: the host clock around `--reps` blocking runs, per call;
  * `device_us`: the median duration of the program's `XLA Modules`
    events in a profiler trace of `--reps` more runs, per call, averaged
    over the chips (null where the trace holds no TPU plane).

Every form's result must equal XLA's own collective's (`matches_native`).
Beside each it prints the form's price (`Selector.price_program`) under
the communicator's `HwSpec` and under the modelled `TPU_V5E`, and the
selector's own pick at that size. One
JSON line per form, then one summary line; `--out` also writes them.

    python3 scripts/stream_sweep.py --out sweep.json

On four v5e chips this is one process that holds all four. On the CPU,
`JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4
python3 scripts/stream_sweep.py --bytes 65536` rehearses the control flow:
its host times are the CPU's, and it has no device times.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

AX = "x"

# (collective, algorithm, segments); backend "native" forms are XLA's own
# collective, timed as the reference the microcode is compared with.
FORMS = (
    [("allreduce", "bidi_ring", k) for k in (1, 2, 4, 8)]
    + [("reduce_scatter", "ring", k) for k in (1, 2, 4, 8)]
    + [("reduce_scatter", "recursive_halving", 1)]
    + [("allgather", "ring", k) for k in (1, 2, 4, 8)]
    + [("allgather", "recursive_doubling", 1)]
    + [("alltoall", "linear", k) for k in (1, 2, 4, 8)]
    + [(c, "native", 1) for c in ("allreduce", "reduce_scatter",
                                  "allgather", "alltoall")]
)


def _link(eng, coll, algo, k, n):
    """One link of the chain, the collective grid's glue around the call."""
    import jax
    import jax.numpy as jnp

    kw = {} if algo == "native" else {"algorithm": algo, "segments": k}

    def link(x):
        r = jax.lax.axis_index(AX)
        if coll == "allreduce":
            return eng.allreduce(x, AX, **kw)
        if coll == "reduce_scatter":
            return jnp.tile(eng.reduce_scatter(x, AX, **kw), n)
        if coll == "allgather":
            y = eng.allgather(x, AX, **kw).reshape(n, -1)
            return jnp.take(y, (r + 1) % n, axis=0) + x
        if coll == "alltoall":
            return eng.alltoall(x, AX, **kw) + x
        raise ValueError(coll)

    return link


def _program(eng, coll, algo, k, n, chain):
    link = _link(eng, coll, algo, k, n)

    def body(v):
        x = v[0]
        for _ in range(chain):
            x = link(x)
        return x[None]

    body.__name__ = f"sweep_{coll}_{algo}_k{k}"
    from jax.sharding import PartitionSpec as P
    return eng.run(body, in_specs=P(AX), out_specs=P(AX)), body.__name__


def _prices_us(eng, coll, algo, k, nbytes):
    """The form's price under the engine's HwSpec and under the modelled
    TPU_V5E, and its compiled shape."""
    import dataclasses
    from repro.core import algorithms as algos
    from repro.core.hw_spec import TPU_V5E
    comm = eng.comm(AX)
    prog = algos.GENERATORS[(coll, algo)](comm).with_segments(k).compile()
    return [eng.selector.price_program(prog, "rendezvous", nbytes, c) * 1e6
            for c in (comm, dataclasses.replace(comm, hw=TPU_V5E))] + [
        prog.describe().replace("\n", " | ")]


def _device_us(devices, module, chain):
    """Median `XLA Modules` duration of `module` per chip, mean over
    chips, per call; None without a TPU plane."""
    from bench import trace
    per_chip = []
    for dev in devices.values():
        durs = [d for name, _s, d in dev["modules"]
                if trace.module_name(name) == "jit_" + module]
        if durs:
            per_chip.append(statistics.median(durs))
    if not per_chip:
        return None
    return statistics.fmean(per_chip) / 1e3 / chain


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bytes", type=int, default=4 << 20,
                    help="message bytes per rank (float32)")
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import CollectiveEngine

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs).reshape(n), (AX,))
    micro = CollectiveEngine(mesh)
    native = CollectiveEngine(mesh, backend="native")
    m = args.bytes // 4
    x = jax.device_put(
        np.round(np.random.default_rng(0).uniform(-8, 8, (n, m)))
        .astype(np.float32), NamedSharding(mesh, P(AX)))
    comm = micro.comm(AX)
    picks = {}
    for coll in sorted({f[0] for f in FORMS}):
        c = micro.selector.choose(coll, args.bytes, comm)
        picks[coll] = [c.algorithm, c.segments, c.predicted_s * 1e6]

    rows, fns, outs = [], [], []
    for coll, algo, k in FORMS:
        eng = native if algo == "native" else micro
        fn, name = _program(eng, coll, algo, k, n, args.chain)
        fns.append(fn)
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        compile_s = time.perf_counter() - t0
        outs.append(np.asarray(fn(x)))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            fn(x).block_until_ready()
        host = (time.perf_counter() - t0) / args.reps / args.chain * 1e6
        price, modelled, shape = (
            (None, None, "XLA") if algo == "native"
            else _prices_us(micro, coll, algo, k, args.bytes))
        rows.append({"collective": coll, "algorithm": algo, "segments": k,
                     "price_us": price, "modelled_price_us": modelled,
                     "host_us": host,
                     "compile_s": compile_s, "module": name,
                     "program": shape})
    # every form's chain must end where XLA's own collective's does (the
    # integer-valued sums are exact), so no timed form is a wrong one
    want = {r["collective"]: o for r, o in zip(rows, outs)
            if r["algorithm"] == "native"}
    for r, o in zip(rows, outs):
        r["matches_native"] = bool(np.array_equal(o, want[r["collective"]]))

    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir)
        try:
            for fn in fns:
                for _ in range(args.reps):
                    fn(x).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        from bench import trace
        devices = trace.load(tdir)["devices"]
        for r in rows:
            r["device_us"] = _device_us(devices, r["module"], args.chain)

    d = devs[0]
    summary = {"device": {"platform": d.platform,
                          "device_kind": d.device_kind, "count": n},
               "hw": micro.hw.name, "bytes": args.bytes,
               "chain": args.chain, "reps": args.reps,
               "selector_picks": picks}
    for r in rows:
        print(json.dumps(r))
    print(json.dumps(summary))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump({"rows": rows, **summary}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
