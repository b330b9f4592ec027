"""The paper's collective grid (Figs. 10-11): each engine collective at
each message size, through the engine's public calls on the program's
defaults (`ParallelConfig().backend`, `algorithm="auto"`).

Each (collective, size) is one jitted `eng.run` program that issues the
collective `chain` times back to back, each input made from the last
output at the same size (`reference.coll_step`), so a missing exchange
anywhere in the chain shows in its result. The window runs the programs
in phases of one size class (small: latency-bound; large: link-bound),
each phase whole passes over its programs in a seeded order, blocking
after each program, for at least `phase_min_s`: so every time read off
the host clock spans a quarter second or more.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import reference, weights, work

AX = "x"


def _chain_fn(eng, name: str, n: int, root: int, steps: int):
    """The per-rank body: (1, m) block -> (1, m) after `steps` links."""

    def link(x):
        r = jax.lax.axis_index(AX)
        if name == "allreduce":
            return eng.allreduce(x, AX)
        if name == "reduce_scatter":
            return jnp.tile(eng.reduce_scatter(x, AX), n)
        if name == "allgather":
            y = eng.allgather(x, AX).reshape(n, -1)
            return jnp.take(y, (r + 1) % n, axis=0) + x
        if name == "bcast":
            return eng.bcast(x, AX, root=root) + x
        if name == "reduce":
            return jnp.where(r == root, eng.reduce(x, AX, root=root), x)
        if name == "gather":
            y = eng.gather(x, AX, root=root).reshape(n, -1)
            return jnp.where(r == root,
                             jnp.take(y, (root + 1) % n, axis=0) + x, x)
        if name == "alltoall":
            return eng.alltoall(x, AX) + x
        raise ValueError(name)

    def chain(v):
        x = v[0]
        for _ in range(steps):
            x = link(x)
        return x[None]

    return chain


class Driver:
    """One collective-grid cell: `load` the seed's inputs, `warm` every
    program, run a `window`, then `check` the kept results."""

    def __init__(self, cfg: dict, mix: dict, devices):
        from repro.configs.base import ParallelConfig
        from repro.core import CollectiveEngine

        self.cfg, self.mix = cfg, mix
        self.n = int(cfg["ranks"])
        self.root = int(cfg["root"])
        self.steps = int(mix["chain"])
        mesh = Mesh(np.asarray(devices).reshape(self.n), (AX,))
        self.eng = CollectiveEngine(mesh, backend=ParallelConfig().backend)
        self.sharding = NamedSharding(mesh, P(AX))
        self.programs = []
        for size in cfg["sizes_bytes"]:
            for name in cfg["collectives"]:
                fn = _chain_fn(self.eng, name, self.n, self.root, self.steps)
                fn.__name__ = f"coll_{name}_{size}"
                cls = "large" if size in mix["large_sizes"] else "small"
                self.programs.append({
                    "name": name, "bytes": size, "cls": cls,
                    "fn": self.eng.run(fn, in_specs=P(AX), out_specs=P(AX)),
                    "module": "jit_" + fn.__name__})
        self.inputs = None

    def load(self, seed: int, seconds: float) -> None:
        """Integer-valued float32 inputs in [-r, r] from the seed: every
        sum in a chain stays exact in float32 (below 2**24)."""
        self.seed = seed
        vr = int(self.mix["value_range"])
        self.inputs = []
        for i, p in enumerate(self.programs):
            m = p["bytes"] // 4
            x = weights.uniform_np(weights.key_of(seed, 100 + i),
                                   (self.n, m), 1.0)
            x = np.round(x * vr).astype(np.float32)
            p["x_host"] = x
            self.inputs.append(jax.device_put(x, self.sharding))

    def warm(self) -> None:
        for p, x in zip(self.programs, self.inputs):
            p["fn"](x).block_until_ready()

    def window(self, seconds: float, annotate) -> dict:
        rng = np.random.default_rng([self.seed, 11])
        by_cls = {c: [i for i, p in enumerate(self.programs) if p["cls"] == c]
                  for c in ("small", "large")}
        calls = {c: 0 for c in by_cls}
        busy = {c: 0.0 for c in by_cls}
        bus = {c: 0.0 for c in by_cls}
        kept = {}
        clock = time.perf_counter
        t_start = clock()
        while clock() - t_start < seconds:
            for c, idxs in by_cls.items():
                t0 = clock()
                while clock() - t0 < self.mix["phase_min_s"]:
                    for i in rng.permutation(idxs):
                        p = self.programs[i]
                        with annotate("dispatch"):
                            out = p["fn"](self.inputs[i])
                        with annotate("wait"):
                            out.block_until_ready()
                        kept[i] = out
                        calls[c] += self.steps
                        bus[c] += self.steps * work.bus_bytes(
                            p["name"], p["bytes"], self.n)
                busy[c] += clock() - t0
        return {"calls": calls, "busy": busy, "bus": bus, "kept": kept,
                "seconds": clock() - t_start}

    def end_to_end(self, win: dict) -> dict:
        return {
            "coll_small_us": win["busy"]["small"] / win["calls"]["small"]
            * 1e6,
            "coll_large_GBps": win["bus"]["large"] / win["busy"]["large"]
            / 1e9,
        }

    def counts(self, win: dict) -> tuple[int, int]:
        return sum(win["calls"].values()), 0

    def classes(self) -> dict:
        return {}

    def release(self) -> None:
        self.inputs = None

    def check(self, win: dict) -> dict:
        """Every program's last result in the window against the numpy
        oracle of its chain, element for element (the sums are exact)."""
        bad = 0
        for i, p in enumerate(self.programs):
            want = reference.coll_chain(p["name"], p["x_host"], self.root,
                                        self.steps)
            out = win["kept"].get(i)
            got = np.asarray(out) if out is not None else None
            bad += int(want.size) if got is None \
                else int((got.astype(np.float64) != want).sum())
        return {"coll_mismatches": (bad, self.cfg["check"]["mismatch_limit"])}

    def work(self) -> dict:
        return {"ranks": self.n, "chain": self.steps,
                "programs": [{k: p[k] for k in ("name", "bytes", "cls",
                                                "module")}
                             for p in self.programs]}

