"""DLRM inference serving: the paper's use case 2 through the program's
normal path, `jit(shard_map(dlrm_forward))` over a `CollectiveEngine`,
built on the program's defaults (`ParallelConfig()`, the engine's
`algorithm="auto"`).

One client in a closed loop: each batch of ids goes from host memory to
the device, through the serving step, and back as logits before the next
is sent. The latency of a batch is that whole round trip.
"""
from __future__ import annotations

import contextlib
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import opclass, reference, traffic, weights, work


def _model_config(cfg: dict):
    from repro.configs.dlrm import DLRMConfig
    return DLRMConfig(n_tables=cfg["n_tables"], emb_dim=cfg["emb_dim"],
                      rows_per_table=cfg["rows_per_table"],
                      fc_dims=tuple(cfg["fc_dims"]), out_dim=cfg["out_dim"])


def _param_maker(shapes):
    """jit: keys -> the program's parameter tree, every leaf made on the
    device in place under its sharding from `reference`'s streams."""

    def gen(key, sds, scale):
        idx = tuple(jax.lax.broadcasted_iota(jnp.uint32, sds.shape, a)
                    for a in range(len(sds.shape)))
        return weights.to_uniform(weights.hash_index(key, idx, jnp), scale,
                                  jnp)

    def make(keys):
        fc = []
        for i, layer in enumerate(shapes["fc"]):
            ws, bs = reference.fc_streams(i)
            fc.append({"w": gen(keys[ws], layer["w"],
                                reference.fc_scale(layer["w"].shape[0])),
                       "b": gen(keys[bs], layer["b"], reference.BIAS_SCALE)})
        return {"tables": gen(keys[reference.TABLE_STREAM], shapes["tables"],
                              reference.TABLE_SCALE), "fc": fc}

    shardings = jax.tree.map(lambda s: s.sharding, shapes)
    return jax.jit(make, out_shardings=shardings)


class Driver:
    """One DLRM serving cell: `load` the seed's data, `warm` every shape,
    run a `window`, then `check` what it served."""

    def __init__(self, cfg: dict, mix: dict, devices):
        from repro.configs.base import ParallelConfig
        from repro.core import CollectiveEngine
        from repro.models import dlrm as dm
        from repro.models.common import Builder
        from repro.parallel.ops import ParCtx

        self.cfg, self.mix = cfg, mix
        self.batch = int(mix["batch"])
        self.mcfg = _model_config(cfg)
        mesh = Mesh(np.asarray(devices).reshape(cfg["mesh"]["shape"]),
                    tuple(cfg["mesh"]["axes"]))
        pcfg = ParallelConfig()
        ctx = ParCtx(engine=CollectiveEngine(mesh, backend=pcfg.backend),
                     pcfg=pcfg, mesh=mesh)
        tp = mesh.shape["model"]
        specs = dm.dlrm_specs(self.mcfg, tp)
        self.shapes = dm.dlrm_params(Builder("shape", mesh=mesh), self.mcfg,
                                     tp)

        # The argument names are what `opclass.dlrm_seed_tags` reads.
        def dlrm_serve_step(params, ids):
            return dm.dlrm_forward(params, ids, ctx, pcfg.use_pallas)

        self.serve = jax.jit(jax.shard_map(
            dlrm_serve_step, mesh=mesh, in_specs=(specs, P(None, None)),
            out_specs=P(None, None), check_vma=False))
        self.ids_sharding = NamedSharding(mesh, P(None, None))
        self.make_params = _param_maker(self.shapes)
        ids = mix["ids"]
        self.sampler = traffic.id_sampler(
            cfg["rows_per_table"], cfg["n_tables"], ids["dist"],
            ids.get("alpha", 0.0))
        self.params = self.pool = None

    # -- set-up ---------------------------------------------------------
    def load(self, seed: int, seconds: float) -> None:
        """The seed's traffic (every batch the window can use, and the
        warm-up's) and parameters, made on the device."""
        self.seed = seed
        self.params = None
        t0 = time.perf_counter()
        n = math.ceil(seconds * self.mix["pool_batches_per_s"]) \
            + self.mix["warm_batches"]
        self.pool = traffic.IdPool(
            self.sampler, traffic.id_keys(seed, self.cfg["n_tables"]), n,
            self.batch, self.cfg["n_tables"])
        t1 = time.perf_counter()
        self.keys = reference.dlrm_keys(seed, len(self.shapes["fc"]))
        self.params = jax.block_until_ready(self.make_params(self.keys))
        self.phases = {"ids_pool_s": t1 - t0,
                       "params_s": time.perf_counter() - t1}

    def warm(self) -> None:
        """Compile and run the serving step on the warm-up batches, the
        pool's last ones (the window starts at the first)."""
        n = len(self.pool)
        for i in range(n - self.mix["warm_batches"], n):
            self._serve_one(self.pool[i],
                            lambda name: contextlib.nullcontext())

    def _serve_one(self, ids_host, annotate):
        with annotate("ids_to_device"):
            ids = jax.device_put(ids_host, self.ids_sharding)
        with annotate("dispatch"):
            out = self.serve(self.params, ids)
        with annotate("wait"):
            out.block_until_ready()
        return out

    # -- the measured window ---------------------------------------------
    def window(self, seconds: float, annotate) -> dict:
        lat, outs = [], []
        i = 0
        clock = time.perf_counter
        t_start = clock()
        t_end = t_start + seconds
        t1 = t_start
        while t1 < t_end:
            t0 = clock()
            outs.append(self._serve_one(self.pool.take(i), annotate))
            t1 = clock()
            lat.append(t1 - t0)
            i += 1
        return {"lat": np.asarray(lat), "outs": outs, "batches": i,
                "seconds": t1 - t_start, "refills": self.pool.refills}

    def end_to_end(self, win: dict) -> dict:
        return {
            "dlrm_qps": win["batches"] * self.batch / win["seconds"],
            "dlrm_p95_ms": float(np.percentile(win["lat"], 95)) * 1e3,
        }

    def counts(self, win: dict) -> tuple[int, int]:
        """(queries attempted, queries without a finite logit)."""
        outs = np.concatenate([np.asarray(o) for o in win["outs"]])
        return outs.shape[0], int((~np.isfinite(outs)).any(axis=1).sum())

    # -- after the window ---------------------------------------------------
    def classes(self) -> dict:
        """module name -> {instruction: class} of the serving step."""
        ids = jax.device_put(self.pool[0], self.ids_sharding)
        text = self.serve.lower(self.params, ids).compile().as_text()
        c = opclass.classify(text, opclass.dlrm_seed_tags)
        return {c["module"]: c["classes"]}

    def release(self) -> None:
        self.params = None

    def sample(self, win: dict) -> np.ndarray:
        """Batch indices to compare, drawn from the seed among the served
        ones: `check_queries` queries' worth."""
        k = min(win["batches"],
                math.ceil(self.mix["check_queries"] / self.batch))
        rng = np.random.default_rng([self.seed, 7])
        return np.sort(rng.choice(win["batches"], size=k, replace=False))

    def check(self, win: dict) -> dict:
        """The compared numbers: {name: (value, limit)}."""
        idx = self.sample(win)
        got = np.concatenate([np.asarray(win["outs"][i]) for i in idx])
        ids = np.concatenate([self.pool[i] for i in idx])
        fc = reference.dlrm_fc_np(self.keys, work.fc_dims(self.cfg))
        want = np.concatenate([
            reference.dlrm_logits(self.keys, fc, ids[j:j + 256],
                                  self.cfg["emb_dim"])
            for j in range(0, len(ids), 256)])
        gap = reference.logit_gap(got, want) if np.isfinite(got).all() \
            else float("inf")
        return {"logit_gap": (gap, self.cfg["check"]["logit_gap_limit"])}

    def work(self) -> dict:
        return {
            "batch": self.batch,
            "flops_per_query": work.dlrm_flops_per_query(self.cfg),
            "lookup_bytes": work.dlrm_lookup_bytes(self.cfg, self.batch),
        }
