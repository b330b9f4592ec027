"""Step builders: full-mesh shard_map train_step / prefill / decode_step.

The whole step runs inside ONE shard_map over the production mesh
(manual-GSPMD): every collective — FSDP gathers, TP reductions, EP
all-to-alls, DP gradient sync — is issued by the CollectiveEngine
(backend='microcode' = the paper's CCLO; 'native' = XLA's built-ins, the
software-MPI baseline).

Gradient sync rule (validated in tests/test_grad_semantics.py): a param's
gradient must be psum'd over every mesh axis absent from its PartitionSpec.
Leaves are bucketed by their missing-axis set and synced with ONE fused
engine allreduce per bucket (gradient bucketing), optionally
int8/bf16-compressed (the paper's unary streaming plugin as a distributed-
optimization trick). By default the buckets go through the engine's
non-blocking request queue (`itree_allreduce`): all groups issue before
any waits, the paper's offload-engine enqueue-then-overlap pattern
(`ParallelConfig.async_grad_sync`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ParallelConfig
from repro.core.engine import CollectiveEngine
from repro.models import lm as lm_mod
from repro.models import serve as serve_mod
from repro.models.common import Builder, dt, sharded_initializer
from repro.optim import adamw
from repro.parallel.ops import ParCtx, spec_axes


def make_ctx(cfg: ArchConfig, pcfg: ParallelConfig, mesh) -> ParCtx:
    engine = CollectiveEngine(mesh, backend=pcfg.backend,
                              use_pallas=pcfg.use_pallas)
    return ParCtx(engine=engine, pcfg=pcfg, mesh=mesh)


# --------------------------------------------------------------------------
# Params in three modes
# --------------------------------------------------------------------------

def _drop_data_axis(spec: P) -> P:
    return P(*(None if e == "data" else e for e in spec))


def param_specs(cfg: ArchConfig, tp: int, serve: bool = False):
    specs = lm_mod.model_params(Builder("spec"), cfg, tp)
    if serve:
        # serving layout: weights replicated over 'data' (pure TP) — no
        # ZeRO-3 gathers on the token path
        specs = jax.tree.map(_drop_data_axis, specs,
                             is_leaf=lambda x: isinstance(x, P))
    return specs


def param_shapes(cfg: ArchConfig, mesh, tp: int, dtype=None,
                 serve: bool = False):
    b = Builder("shape", mesh=mesh, dtype=dtype or dt(cfg.param_dtype))
    shapes = lm_mod.model_params(b, cfg, tp)
    if serve:
        specs = param_specs(cfg, tp, serve=True)
        shapes = jax.tree.map(
            lambda sd, sp: jax.ShapeDtypeStruct(
                sd.shape, sd.dtype,
                sharding=NamedSharding(mesh, sp)),
            shapes, specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return shapes


def init_params(cfg: ArchConfig, mesh, tp: int, seed: int = 0):
    """Real init, under jit, straight into the spec shardings."""
    init = sharded_initializer(lambda b: lm_mod.model_params(b, cfg, tp),
                               mesh, param_specs(cfg, tp), dt(cfg.param_dtype))
    return init(jax.random.PRNGKey(seed))


# --------------------------------------------------------------------------
# Gradient sync
# --------------------------------------------------------------------------

def grad_sync(grads, specs, ctx: ParCtx,
              compression: Optional[str] = None,
              use_queue: bool = True):
    """Bucketed, engine-routed gradient synchronization.

    With `use_queue` (`ParallelConfig.async_grad_sync`), every sync
    group's bucketed allreduces are ISSUED into the engine's request
    queue first (`itree_allreduce` — the non-blocking CCLO offload
    path) and only then waited: all gradient buckets sit in the queue
    together, so small same-dtype buckets coalesce into one program and
    independent buckets drain back-to-back without per-call re-entry.
    The queue's coalescing eligibility rule makes this bitwise-identical
    to the blocking path.

    Returns (synced grads, psum-corrected local sum-of-squares for the
    global clip norm: each leaf's contribution divided by its replication
    factor so one allreduce over the full mesh yields the true norm).
    """
    mesh_axes = [a for a in ctx.mesh.axis_names if ctx.mesh.shape[a] > 1]
    flat, treedef = jax.tree.flatten_with_path(grads)
    spec_flat = {tuple(p): s for p, s in jax.tree.flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]}

    buckets: dict = {}
    for path, leaf in flat:
        spec = spec_flat[tuple(path)]
        missing = tuple(a for a in mesh_axes if a not in spec_axes(spec))
        buckets.setdefault(missing, []).append((path, leaf))

    # issue phase: enqueue every sync group's bucket collectives before
    # materializing any (the backward walk's grads are all live here, so
    # the whole gradient exchange is outstanding at once — the paper's
    # enqueue-then-overlap offload pattern)
    tickets = {}
    for missing, entries in buckets.items():
        if not missing:
            continue
        leaves = [l for _, l in entries]
        # fastest (ICI) axes first, pod (DCN) last. A two-axis group
        # (("data", "pod") — the cross-pod data-parallel bucket) folds
        # into ONE hierarchical request over the product communicator:
        # a single two-level program whose DCN phase carries 1/|data|
        # of the bucket bytes (engine.allreduce_multi / issue_multi).
        order = [a for a in ("data", "model") if a in missing] + \
                [a for a in missing if a not in ("data", "model")]
        if use_queue:
            tickets[missing] = ctx.engine.itree_allreduce(
                leaves, order, compression=compression)
        else:
            tickets[missing] = ctx.engine.tree_allreduce(
                leaves, order, compression=compression)

    if use_queue and tickets:
        # mesh-level price of the outstanding gradient exchange: every
        # sync group's queue composed over the shared fabrics (the
        # contention-aware view, not per-axis optimism). Trace-time
        # telemetry off static shapes — no tracers involved; the trainer
        # surfaces it per step (`Trainer._queue_stats`).
        from repro.core.mesh_cost import MeshMakespan
        ctx.engine.metrics.set("grad_sync_makespan_s",
                               MeshMakespan.of(ctx.engine.queue).total())

    out = {}
    sq = jnp.zeros((), jnp.float32)
    for missing, entries in buckets.items():
        repl = 1
        for a in missing:
            repl *= ctx.mesh.shape[a]
        if missing:
            t = tickets[missing]
            synced = t.wait() if use_queue else t
        else:
            synced = [l for _, l in entries]
        for (path, _), s in zip(entries, synced):
            out[tuple(path)] = s
            sq = sq + jnp.sum(jnp.square(s.astype(jnp.float32))) / repl

    ordered = [out[tuple(p)] for p, _ in flat]
    return jax.tree.unflatten(treedef, ordered), sq


# --------------------------------------------------------------------------
# Train step
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TrainStep:
    fn: object            # jitted shard_map step
    ctx: ParCtx
    specs: object         # param PartitionSpec tree
    opt_specs: object
    batch_spec: object


def build_train_step(cfg: ArchConfig, pcfg: ParallelConfig, mesh,
                     opt_cfg: adamw.AdamWConfig,
                     lr_schedule=None) -> TrainStep:
    ctx = make_ctx(cfg, pcfg, mesh)
    tp = ctx.tp
    specs = param_specs(cfg, tp)
    ospecs = adamw.opt_specs(specs)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = lm_mod.batch_specs(cfg, "train", dp=dp)

    def step(params, opt_state, batch, step_idx):
        def lf(p, mb):
            return lm_mod.loss_fn(p, mb, cfg, ctx)

        k = pcfg.microbatches
        if k <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                lf, has_aux=True)(params, batch)
        else:
            # gradient accumulation: per-microbatch backward inside the
            # scan body (no cross-microbatch residuals), grads averaged
            def split(leaf):
                b = leaf.shape[0]
                return leaf.reshape((k, b // k) + leaf.shape[1:])

            mbs = jax.tree.map(split, batch)

            def acc_body(carry, mb):
                g_acc, l_acc, m_acc = carry
                (l, m), g = jax.value_and_grad(lf, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                m_acc = jax.tree.map(lambda a, b: a + b, m_acc, m)
                return (g_acc, l_acc + l, m_acc), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"ce_mean": jnp.zeros((), jnp.float32),
                  "aux": jnp.zeros((), jnp.float32)}
            (grads, loss, metrics), _ = jax.lax.scan(
                acc_body, (g0, jnp.zeros((), jnp.float32), m0), mbs)
            grads = jax.tree.map(lambda g: g / k, grads)
            loss = loss / k
            metrics = jax.tree.map(lambda m: m / k, metrics)
        grads, sq_local = grad_sync(grads, specs, ctx,
                                    compression=pcfg.grad_compression,
                                    use_queue=pcfg.async_grad_sync)
        # global clip norm: one scalar allreduce over the whole mesh
        axes = [a for a in mesh.axis_names if mesh.shape[a] > 1]
        sq = sq_local
        for a in axes:
            sq = ctx.engine.allreduce(sq, a)
        gnorm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, opt_cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads)

        lr_scale = lr_schedule(step_idx) if lr_schedule else 1.0
        cfg_noclip = dataclasses.replace(opt_cfg, grad_clip=1e30)
        opt_state, _ = adamw.adamw_update(cfg_noclip, grads, opt_state,
                                          lr_scale=lr_scale)
        params = adamw.apply_updates(opt_state, dt(cfg.param_dtype))
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["loss"] = loss
        return params, opt_state, metrics

    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(specs, ospecs, bspec, P()),
        out_specs=(specs, ospecs, jax.tree.map(lambda _: P(), {
            "ce_mean": 0, "aux": 0, "grad_norm": 0, "loss": 0})),
        check_vma=False)
    fn = jax.jit(mapped, donate_argnums=(0, 1))
    return TrainStep(fn=fn, ctx=ctx, specs=specs, opt_specs=ospecs,
                     batch_spec=bspec)


# --------------------------------------------------------------------------
# Serve steps
# --------------------------------------------------------------------------

def dp_axes(mesh, global_batch: int):
    """DP sharding axes for a batch dim; None (replicate) when the batch
    is smaller than the DP group (B=1 long-context decode)."""
    axes = tuple(a for a in ("pod", "data")
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return axes if axes and global_batch % n == 0 else None


def build_prefill(cfg: ArchConfig, pcfg: ParallelConfig, mesh,
                  global_batch: int, seq_len: int):
    pcfg = dataclasses.replace(pcfg, serving=True)
    ctx = make_ctx(cfg, pcfg, mesh)
    specs = param_specs(cfg, ctx.tp, serve=True)
    dp = dp_axes(mesh, global_batch)
    bspec = lm_mod.batch_specs(cfg, "prefill", dp=dp)
    cspec = serve_mod.prefill_cache_specs(cfg, pcfg, ctx.tp, seq_len, dp=dp)

    def pf(params, batch):
        return serve_mod.prefill(params, batch, cfg, ctx)

    mapped = jax.shard_map(pf, mesh=mesh, in_specs=(specs, bspec),
                       out_specs=(P(dp), cspec), check_vma=False)
    return jax.jit(mapped), ctx, specs, bspec


def cache_specs(cfg: ArchConfig, pcfg: ParallelConfig, tp: int,
                s_max: int, s_enc: int = 0, dp=("pod", "data")):
    b = Builder("spec")
    return serve_mod.make_cache(b, cfg, tp, 0, s_max, pcfg, s_enc=s_enc,
                                dp=dp)


def cache_shapes(cfg: ArchConfig, pcfg: ParallelConfig, mesh, tp: int,
                 batch: int, s_max: int, s_enc: int = 0, dp=("pod", "data")):
    b = Builder("shape", mesh=mesh, dtype=dt(cfg.param_dtype))
    return serve_mod.make_cache(b, cfg, tp, batch, s_max, pcfg, s_enc=s_enc,
                                dp=dp)


def init_cache(cfg: ArchConfig, pcfg: ParallelConfig, mesh, tp: int,
               batch: int, s_max: int, s_enc: int = 0):
    dp = dp_axes(mesh, batch)
    b = Builder("init", key=jax.random.PRNGKey(0), dtype=dt(cfg.param_dtype))
    cache = serve_mod.make_cache(b, cfg, tp, batch, s_max, pcfg,
                                 s_enc=s_enc, dp=dp)
    cspecs = cache_specs(cfg, pcfg, tp, s_max, s_enc=s_enc, dp=dp)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        cache, cspecs, is_leaf=lambda x: isinstance(x, jnp.ndarray))


def build_decode_step(cfg: ArchConfig, pcfg: ParallelConfig, mesh,
                      s_max: int, global_batch: int, s_enc: int = 0):
    pcfg_d = dataclasses.replace(pcfg, sequence_parallel=False,
                                 serving=True)
    ctx = make_ctx(cfg, pcfg_d, mesh)
    specs = param_specs(cfg, ctx.tp, serve=True)
    dp = dp_axes(mesh, global_batch)
    cspecs = cache_specs(cfg, pcfg_d, ctx.tp, s_max, s_enc=s_enc, dp=dp)

    def dstep(params, caches, tokens, pos):
        return serve_mod.decode_step(params, caches, tokens, pos, cfg, ctx,
                                     s_max)

    mapped = jax.shard_map(
        dstep, mesh=mesh,
        in_specs=(specs, cspecs, P(dp, None), P()),
        out_specs=(P(dp), cspecs),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(1,)), ctx, specs, cspecs
