"""DLRM-DCNv2 serving (MLPerf, Criteo 1TB multi-hot) on a 'model' axis
of chips, through the program's normal path: `jit(shard_map(
dlrm_forward))` over a `CollectiveEngine`, on the program's defaults
(`ParallelConfig()`, the engine's `algorithm="auto"`).

Every table is row-sharded over the chips; each chip pools its part of
every bag of the global batch, the engine's reduce-scatter hands each
chip the pooled bags of its quarter of the samples, and the dense part
runs there on those samples.

One client in a closed loop: each batch (ids and dense features) goes
from host memory to the devices, through the serving step, and back as
logits before the next is sent. The latency of a batch is that whole
round trip.
"""
from __future__ import annotations

import contextlib
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import opclass, reference, reference_dcnv2, scopes, traffic, \
    weights, work_dcnv2
from bench.drivers import dlrm_serve

# The pools are made in chunks of this size: XLA:TPU's compile time of a
# sampler grows with its chunk's shape (a 256 MB chunk of these bags
# compiles in over a minute, 16 MB in seconds).
POOL_CHUNK_BYTES = 16 << 20

MODEL_KEYS = ("n_tables", "emb_dim", "dense_features", "fc_dims", "out_dim",
              "table_rows", "multi_hot", "bottom_dims", "interaction",
              "dcn_layers", "dcn_rank")


def _model_config(cfg: dict):
    from repro.configs.dlrm import DLRMConfig
    return DLRMConfig(**{k: tuple(cfg[k]) if isinstance(cfg[k], list)
                         else cfg[k] for k in MODEL_KEYS})


def multihot_sampler(rows, hots, dist: str, alpha: float = 0.0):
    """(keys, first batch, n batches, batch) -> int32 ids (n, batch *
    sum(hots)) on the host: each query's bags side by side in table order,
    table t's bag of `hots[t]` ids drawn over its `rows[t]` rows by
    `traffic.id_sampler` (bounded Zipf through the table's seeded
    permutation, or uniform), each id of a bag a draw of its own (the
    one-table sampler over batch * hots[t] draws). keys[t] is table t's
    (draw key, permutation key). Each table's draws are a jitted program
    of their own, joined on the host: XLA compiles the 26 fused into one
    program ~30x slower than one by one."""
    per_table = [traffic.id_sampler(r, 1, dist, alpha) for r in rows]

    def sample(keys, first, n, batch):
        parts = [s(k, first, n, batch * m)
                 for s, k, m in zip(per_table, keys, hots)]
        return np.concatenate(
            [np.asarray(p).reshape(n, batch, m)
             for p, m in zip(parts, hots)], axis=2).reshape(n, -1)

    return sample


def multihot_keys(seed: int, tables: int) -> list:
    return [np.array([weights.key_of(seed, 3000 + t),
                      weights.key_of(seed, 2000 + t)], np.uint32)
            for t in range(tables)]


def dense_sampler(features: int, high: float):
    """jit: (key, first batch, n, batch) -> float32 dense features (n,
    batch * features), uniform in [0, high) (a power of two: exact)."""
    half = high / 2

    def sample(key, first, n, batch):
        shape = (n, batch, features)
        b = jax.lax.broadcasted_iota(jnp.uint32, shape, 0) + first
        q = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        f = jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
        h = weights.hash_index(key, (b, q, f), jnp)
        x = weights.to_uniform(h, half, jnp) + jnp.float32(half)
        return x.reshape(n, batch * features)

    return jax.jit(sample, static_argnums=(2, 3))


def _param_maker(shapes, cfg: dict, layout):
    """jit: keys -> the program's parameter tree, every leaf made on the
    device in place under its sharding. Packed table row p (shard s =
    p // rows_l, local q) holds table t's row r = s * per[t] + q - off[t]
    (`repro.models.dlrm.table_layout`), made as `reference_dcnv2`
    defines row r of table t."""
    per, off, rows_l = layout

    def gen(key, sds, scale):
        idx = tuple(jax.lax.broadcasted_iota(jnp.uint32, sds.shape, a)
                    for a in range(len(sds.shape)))
        return weights.to_uniform(weights.hash_index(key, idx, jnp), scale,
                                  jnp)

    def tables(key, sds):
        n, dim = sds.shape
        p = jax.lax.broadcasted_iota(jnp.uint32, (n, 1), 0)
        d = jax.lax.broadcasted_iota(jnp.uint32, (1, dim), 1)
        s, q = p // jnp.uint32(rows_l), p % jnp.uint32(rows_l)
        t = jnp.zeros_like(q)
        first, size = jnp.uint32(off[0]), jnp.uint32(per[0])
        for i in range(1, len(per)):
            past = q >= jnp.uint32(off[i])
            t = t + past.astype(jnp.uint32)
            first = jnp.where(past, jnp.uint32(off[i]), first)
            size = jnp.where(past, jnp.uint32(per[i]), size)
        r = s * size + q - first
        return weights.to_uniform(weights.hash_index(key, (t, r, d), jnp),
                                  reference.TABLE_SCALE, jnp)

    def make(keys):
        ids = reference_dcnv2.streams(cfg)
        dense = reference_dcnv2.dense_shapes(cfg)
        names = {"bottom": ("w", "b"), "cross": ("v", "w", "b"),
                 "top": ("w", "b")}
        out = {"tables": tables(keys[reference.TABLE_STREAM],
                                shapes["tables"])}
        for group, leaf in (("bottom", "bottom"), ("cross", "cross"),
                            ("top", "fc")):
            out[leaf] = [
                {n: gen(keys[s], shapes[leaf][i][n], scale)
                 for n, s, (_, scale) in zip(names[group], ids[group][i],
                                             dense[group][i])}
                for i in range(len(ids[group]))]
        return out

    shardings = jax.tree.map(lambda s: s.sharding, shapes)
    return jax.jit(make, out_shardings=shardings)


def scope_class(path: tuple) -> str:
    """A device op's class from its scope path, under the Table 2 cells'
    class names so that one reader reads the layer in every DLRM cell:
    the engine's collective (`dist`), the bags (`lookup`), the bottom,
    cross and top layers (`fc`), anything else (`other`)."""
    own = scopes.owner(path)
    if own.startswith("engine."):
        return "dist"
    if own == "dlrm.bag":
        return "lookup"
    if own.startswith(("dlrm.bottom", "dlrm.cross", "dlrm.top")):
        return "fc"
    return "other"


class Driver(dlrm_serve.Driver):
    """One DLRM-DCNv2 serving cell: `load` the seed's data, `warm` every
    shape, run a `window`, then `check` what it served. The end-to-end
    numbers, the counts, the sample checked and `release` are the Table 2
    driver's; each batch here is ids and dense features."""

    def __init__(self, cfg: dict, mix: dict, devices):
        from repro.configs.base import ParallelConfig
        from repro.core import CollectiveEngine
        from repro.models import dlrm as dm
        from repro.models.common import Builder
        from repro.parallel.ops import ParCtx

        self.cfg, self.mix = cfg, mix
        self.batch = int(mix["batch"])
        self.mcfg = mcfg = _model_config(cfg)
        self.mesh = mesh = Mesh(
            np.asarray(devices).reshape(cfg["mesh"]["shape"]),
            tuple(cfg["mesh"]["axes"]))
        pcfg = ParallelConfig()
        ctx = ParCtx(engine=CollectiveEngine(mesh, backend=pcfg.backend),
                     pcfg=pcfg, mesh=mesh)
        self.chips = tp = mesh.shape["model"]
        self.specs = dm.dlrm_specs(mcfg, tp)
        self.shapes = dm.dlrm_params(Builder("shape", mesh=mesh), mcfg, tp)

        def dlrm_dcnv2_serve_step(params, ids, dense):
            return dm.dlrm_forward(params, ids, ctx, pcfg.use_pallas,
                                   dense=dense, cfg=mcfg)

        self.serve = jax.jit(jax.shard_map(
            dlrm_dcnv2_serve_step, mesh=mesh,
            in_specs=(self.specs, P(None, None), P("model", None)),
            out_specs=P("model", None), check_vma=False))
        self.ids_sharding = NamedSharding(mesh, P(None, None))
        self.dense_sharding = NamedSharding(mesh, P("model", None))
        self.make_params = _param_maker(self.shapes, cfg,
                                        dm.table_layout(mcfg, tp))
        ids, dense = mix["ids"], mix["dense"]
        self.sampler = multihot_sampler(
            mcfg.rows, mcfg.hots, ids["dist"], ids.get("alpha", 0.0))
        self.dense_sampler = dense_sampler(mcfg.dense_features,
                                           dense["high"])
        self.params = self.pool = self.dense = None

    # -- set-up ---------------------------------------------------------
    def load(self, seed: int, seconds: float) -> None:
        """The seed's traffic (every batch the window can use, and the
        warm-up's) and parameters, made on the devices."""
        self.seed = seed
        self.params = None
        t0 = time.perf_counter()
        n = math.ceil(seconds * self.mix["pool_batches_per_s"]) \
            + self.mix["warm_batches"]
        self.pool = traffic.IdPool(
            self.sampler, multihot_keys(seed, self.mcfg.n_tables), n,
            self.batch, self.mcfg.ids_per_query, POOL_CHUNK_BYTES)
        self.dense = traffic.IdPool(
            self.dense_sampler, weights.key_of(seed, 4000), n, self.batch,
            self.mcfg.dense_features, POOL_CHUNK_BYTES)
        t1 = time.perf_counter()
        self.keys = reference_dcnv2.keys(seed, self.cfg)
        self.params = jax.block_until_ready(self.make_params(self.keys))
        self.phases = {"ids_pool_s": t1 - t0,
                       "params_s": time.perf_counter() - t1}

    def warm(self) -> None:
        """Compile and run the serving step on the warm-up batches, the
        pools' last ones (the window starts at the first). The id and
        dense pools are chunked by their own widths, so they can hold a
        few batches more or less than asked: warm on batches both hold."""
        n = min(len(self.pool), len(self.dense))
        for i in range(n - self.mix["warm_batches"], n):
            self._serve_one(self.pool[i], self.dense[i],
                            lambda name: contextlib.nullcontext())

    def _serve_one(self, ids_host, dense_host, annotate):
        with annotate("ids_to_device"):
            ids = jax.device_put(ids_host, self.ids_sharding)
            dense = jax.device_put(dense_host, self.dense_sharding)
        with annotate("dispatch"):
            out = self.serve(self.params, ids, dense)
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
            outs.append(self._serve_one(self.pool.take(i),
                                        self.dense.take(i), annotate))
            t1 = clock()
            lat.append(t1 - t0)
            i += 1
        return {"lat": np.asarray(lat), "outs": outs, "batches": i,
                "seconds": t1 - t_start,
                "refills": self.pool.refills + self.dense.refills}

    # -- after the window ---------------------------------------------------
    def classes(self) -> dict:
        """module name -> {instruction: class} of the serving step, by
        each instruction's scopes (`bench.scopes.scope_map`)."""
        ids = jax.device_put(self.pool[0], self.ids_sharding)
        dense = jax.device_put(self.dense[0], self.dense_sharding)
        text = self.serve.lower(self.params, ids, dense).compile().as_text()
        module = opclass.parse_hlo(text)["module"]
        return {module: {ins: scope_class(path)
                         for ins, path in scopes.scope_map(text).items()}}

    def check(self, win: dict) -> dict:
        """The compared numbers: {name: (value, limit)}."""
        idx = self.sample(win)
        got = np.concatenate([np.asarray(win["outs"][i]) for i in idx])
        ids = np.concatenate([self.pool[i] for i in idx])
        dense = np.concatenate([self.dense[i] for i in idx])
        dense_w = reference_dcnv2.dense_np(self.keys, self.cfg)
        want = np.concatenate([
            reference_dcnv2.logits(self.keys, dense_w, self.cfg,
                                   ids[j:j + 256], dense[j:j + 256])
            for j in range(0, len(ids), 256)])
        gap = reference.logit_gap(got, want) if np.isfinite(got).all() \
            else float("inf")
        return {"logit_gap": (gap, self.cfg["check"]["logit_gap_limit"])}

    def work(self) -> dict:
        return {
            "batch": self.batch,
            "flops_per_query": work_dcnv2.dense_flops_per_query(self.cfg),
            "lookup_bytes": work_dcnv2.bag_bytes_per_chip(
                self.cfg, self.batch, self.chips),
        }
