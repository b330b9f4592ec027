import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512" + (
    (" " + os.environ["XLA_FLAGS"]) if "XLA_FLAGS" in os.environ else "")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

A CPU-only tool. The lines above MUST run before any other import (jax
locks the platform and device count on first init): it pins itself to the
CPU, where 512 virtual host devices back the production meshes (16x16
single-pod, 2x16x16 multi-pod).

Per cell this produces, without allocating any real tensor:
  * compiled.memory_analysis()  -> bytes/device (fits-in-HBM check),
  * compiled.cost_analysis()    -> per-device FLOPs / bytes,
  * parsed collective traffic   -> wire/DCN bytes (launch/analysis.py),
  * the three roofline terms + dominant bottleneck.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs-file f.json]

--all orchestrates one subprocess per cell (fresh XLA, resumable: cells
with an existing result JSON are skipped) and exits non-zero when a cell
failed; a single cell exits non-zero when it failed.
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config  # noqa: E402
from repro.configs.base import ParallelConfig  # noqa: E402
from repro.core.hw_spec import TPU_V5E  # noqa: E402
from repro.launch import analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.common import dt  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.parallel import stages  # noqa: E402

WHISPER_S_ENC = 1500  # 30 s of audio frames (decode cross-attention cache)


def sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def input_specs(cfg, shape_cfg, mesh, pcfg, kind: str):
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    b, s = shape_cfg.global_batch, shape_cfg.seq_len
    dp = stages.dp_axes(mesh, b)
    cdt = dt(cfg.param_dtype)
    if kind in ("train", "prefill"):
        out = {"tokens": sds((b, s), jnp.int32, mesh, P(dp, None))}
        if kind == "train":
            out["labels"] = sds((b, s), jnp.int32, mesh, P(dp, None))
        if cfg.family == "vlm":
            out["vis_embed"] = sds((b, cfg.n_vis_tokens, cfg.d_model), cdt,
                                   mesh, P(dp, None, None))
        if cfg.encoder_layers:
            out["frames"] = sds((b, s, cfg.d_model), cdt, mesh,
                                P(dp, None, None))
        return out
    if kind == "decode":
        return {"tokens": sds((b, 1), jnp.int32, mesh, P(dp, None)),
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    raise ValueError(kind)


def opt_shapes_from(params_shapes):
    def leaf(sd):
        mk = lambda: jax.ShapeDtypeStruct(  # noqa: E731
            sd.shape, jnp.float32, sharding=sd.sharding)
        return {"master": mk(), "m": mk(), "v": mk()}
    leaves = jax.tree.map(
        leaf, params_shapes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return {"leaves": leaves, "count": jax.ShapeDtypeStruct((), jnp.int32)}


def pcfg_from_args(args, backend=None) -> ParallelConfig:
    return ParallelConfig(
        backend=backend or args.backend,
        sequence_parallel=args.sp,
        collective_matmul=args.collective_matmul,
        remat=args.remat,
        grad_compression=args.compress or None,
        attn_q_block=args.q_block,
        attn_kv_block=args.kv_block,
        moe_capacity_factor=args.capacity,
        scan_layers=not args.no_scan,
        decode_seq_shard=not args.no_seq_shard,
        kv_cache_dtype=args.kv_cache,
        microbatches=args.microbatches,
    )


def run_cell(arch_id: str, shape_id: str, multi_pod: bool,
             pcfg: ParallelConfig, variant: str = "base", tp: int = 16):
    t_start = time.time()
    cfg = get_config(arch_id)
    shape_cfg = SHAPES[shape_id]
    mesh = make_production_mesh(multi_pod=multi_pod, tp=tp)
    chips = mesh.size
    pod_size = 256 if multi_pod else 0
    result = {
        "arch": arch_id, "shape": shape_id,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips, "backend": pcfg.backend, "variant": variant,
        "kind": shape_cfg.kind,
    }

    if shape_cfg.kind == "decode" and shape_cfg.seq_len >= 500_000 \
            and not cfg.is_subquadratic:
        result["status"] = "SKIP(full-attn)"
        return result

    tp = mesh.shape["model"]
    serve = shape_cfg.kind != "train"
    pshapes = stages.param_shapes(cfg, mesh, tp, serve=serve)
    s_enc = WHISPER_S_ENC if cfg.encoder_layers else 0

    if shape_cfg.kind == "train":
        ts = stages.build_train_step(cfg, pcfg, mesh,
                                     adamw.AdamWConfig())
        batch = input_specs(cfg, shape_cfg, mesh, pcfg, "train")
        oshapes = opt_shapes_from(pshapes)
        lowered = ts.fn.lower(pshapes, oshapes, batch,
                              jax.ShapeDtypeStruct((), jnp.int32))
    elif shape_cfg.kind == "prefill":
        pf, ctx, _, _ = stages.build_prefill(
            cfg, pcfg, mesh, shape_cfg.global_batch, shape_cfg.seq_len)
        batch = input_specs(cfg, shape_cfg, mesh, pcfg, "prefill")
        lowered = pf.lower(pshapes, batch)
    else:  # decode
        dstep, ctx, _, _ = stages.build_decode_step(
            cfg, pcfg, mesh, s_max=shape_cfg.seq_len,
            global_batch=shape_cfg.global_batch, s_enc=s_enc)
        cshapes = stages.cache_shapes(
            cfg, pcfg, mesh, tp, shape_cfg.global_batch,
            shape_cfg.seq_len, s_enc=s_enc,
            dp=stages.dp_axes(mesh, shape_cfg.global_batch))
        io = input_specs(cfg, shape_cfg, mesh, pcfg, "decode")
        lowered = dstep.lower(pshapes, cshapes, io["tokens"], io["pos"])

    result["t_lower_s"] = round(time.time() - t_start, 2)
    n_active = cfg.n_active_params()
    tokens = shape_cfg.global_batch * (
        shape_cfg.seq_len if shape_cfg.kind != "decode" else 1)
    mult = 6 if shape_cfg.kind == "train" else 2
    return _finish(result, lowered, chips, pod_size,
                   mult * n_active * tokens, t_start)


def _finish(result, lowered, chips, pod_size, model_flops, t_start):
    t0 = time.time()
    compiled = lowered.compile()
    result["t_compile_s"] = round(time.time() - t0, 2)

    mem = compiled.memory_analysis()
    result["memory"] = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "peak_bytes_est": mem.argument_size_in_bytes
        + mem.output_size_in_bytes + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes,
    }
    result["fits_hbm"] = result["memory"]["peak_bytes_est"] \
        < TPU_V5E.hbm_bytes
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    hlo = analysis.analyze_hlo(text, pod_size)
    terms = analysis.roofline_terms(cost, mem, hlo, TPU_V5E, chips)
    result["roofline"] = terms
    result["model_flops"] = model_flops
    gf = terms["global_flops"]
    result["model_flops_ratio"] = model_flops / gf if gf else None
    # scoring roofline: compute / memory-floor / collective (the artifact
    # t_memory_s includes XLA-CPU fusion-boundary rematerialization traffic
    # a TPU backend would keep in VMEM; it is reported as a diagnostic)
    step_time = max(terms["t_compute_s"], terms["t_memory_floor_s"],
                    terms["t_collective_s"])
    result["roofline_step_time_s"] = step_time
    result["roofline_mfu"] = model_flops / (
        chips * TPU_V5E.peak_flops_bf16 * step_time) if step_time else None
    step_art = max(terms["t_compute_s"], terms["t_memory_s"],
                   terms["t_collective_s"])
    result["roofline_mfu_artifact"] = model_flops / (
        chips * TPU_V5E.peak_flops_bf16 * step_art) if step_art else None
    result["hlo_bytes"] = len(text)
    result["status"] = "OK"
    result["t_total_s"] = round(time.time() - t_start, 2)
    return result


def run_dlrm_cell(multi_pod: bool, pcfg: ParallelConfig,
                  variant: str = "base", batch: int = 1024):
    """Paper Table 2 at full scale: 100 tables x 4M rows x 32 (51 GB fp32),
    sharded over the model axis; FC stack checkerboard-decomposed."""
    import dataclasses as _dc
    from jax.sharding import NamedSharding
    from repro.configs.dlrm import CONFIG as dcfg
    from repro.models import dlrm as dlrm_mod
    from repro.models.common import Builder
    from repro.parallel.ops import ParCtx
    from repro.core.engine import CollectiveEngine

    t_start = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    pod_size = 256 if multi_pod else 0
    tp = mesh.shape["model"]
    result = {"arch": "dlrm", "shape": f"serve_b{batch}",
              "mesh": "x".join(str(x) for x in mesh.devices.shape),
              "chips": chips, "backend": pcfg.backend,
              "variant": variant, "kind": "serve"}
    pcfg = _dc.replace(pcfg, serving=True)
    engine = CollectiveEngine(mesh, backend=pcfg.backend)
    ctx = ParCtx(engine=engine, pcfg=pcfg, mesh=mesh)
    specs = dlrm_mod.dlrm_specs(dcfg, tp)
    b = Builder("shape", mesh=mesh, dtype=jnp.float32)
    pshapes = dlrm_mod.dlrm_params(b, dcfg, tp)
    dp = stages.dp_axes(mesh, batch)
    idx = sds((batch, dcfg.n_tables), jnp.int32, mesh, P(dp, None))
    fn = jax.jit(jax.shard_map(
        lambda p, i: dlrm_mod.dlrm_forward(p, i, ctx),
        mesh=mesh, in_specs=(specs, P(dp, None)),
        out_specs=P(dp, None), check_vma=False))
    lowered = fn.lower(pshapes, idx)
    result["t_lower_s"] = round(time.time() - t_start, 2)
    # FC flops (2*b*in*out summed) + embedding gather bytes dominate
    dims = (dcfg.n_tables * dcfg.emb_dim,) + tuple(dcfg.fc_dims) \
        + (dcfg.out_dim,)
    flops = sum(2 * batch * dims[i] * dims[i + 1]
                for i in range(len(dims) - 1))
    return _finish(result, lowered, chips, pod_size, flops, t_start)


def all_cells():
    for arch_id in ARCH_IDS:
        for shape_id in SHAPES:
            yield arch_id, shape_id


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch",
                    choices=sorted(ARCH_IDS) + ["dlrm"])
    ap.add_argument("--shape", choices=sorted(SHAPES),
                    default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--backend", default="microcode",
                    choices=("microcode", "native"))
    ap.add_argument("--variant", default="base")
    ap.add_argument("--results", default="results/dryrun")
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--collective-matmul", action="store_true")
    ap.add_argument("--remat", default="full",
                    choices=("none", "full", "dots", "names"))
    ap.add_argument("--compress", default="")
    ap.add_argument("--q-block", type=int, default=512)
    ap.add_argument("--kv-block", type=int, default=1024)
    ap.add_argument("--capacity", type=float, default=1.25)
    ap.add_argument("--no-scan", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--kv-cache", default="param", choices=("param", "int8"))
    ap.add_argument("--tp", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()
    os.makedirs(args.results, exist_ok=True)

    if args.all:
        failures = []
        for arch_id, shape_id in all_cells():
            tag = "multi" if args.multi_pod else "single"
            name = f"{arch_id}_{shape_id}_{tag}_{args.variant}.json"
            path = os.path.join(args.results, name)
            if os.path.exists(path):
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch_id, "--shape", shape_id,
                   "--backend", args.backend, "--variant", args.variant,
                   "--results", args.results, "--remat", args.remat]
            if args.multi_pod:
                cmd.append("--multi-pod")
            for flag, on in [("--sp", args.sp),
                             ("--collective-matmul", args.collective_matmul),
                             ("--no-scan", args.no_scan),
                             ("--no-seq-shard", args.no_seq_shard)]:
                if on:
                    cmd.append(flag)
            if args.compress:
                cmd += ["--compress", args.compress]
            print(f"[dryrun] {name} ...", flush=True)
            try:
                subprocess.run(cmd, check=True, timeout=args.timeout)
            except Exception as e:  # noqa: BLE001
                failures.append((name, str(e)))
                with open(path, "w") as f:
                    json.dump({"arch": arch_id, "shape": shape_id,
                               "status": f"DRIVER_FAIL: {e}"}, f)
        print(f"[dryrun] done; {len(failures)} failures")
        for n, e in failures:
            print("  FAIL", n, e)
        sys.exit(1 if failures else 0)

    assert args.arch and (args.shape or args.arch == "dlrm"), \
        "--arch and --shape (or --all)"
    pcfg = pcfg_from_args(args)
    tag = "multi" if args.multi_pod else "single"
    shape_tag = args.shape or "serve_b1024"
    name = f"{args.arch}_{shape_tag}_{tag}_{args.variant}.json"
    path = os.path.join(args.results, name)
    try:
        if args.arch == "dlrm":
            result = run_dlrm_cell(args.multi_pod, pcfg, args.variant)
        else:
            result = run_cell(args.arch, args.shape, args.multi_pod, pcfg,
                              args.variant, tp=args.tp)
    except Exception as e:  # noqa: BLE001
        result = {"arch": args.arch, "shape": args.shape,
                  "status": f"FAIL: {type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("traceback", "roofline")}, indent=1))
    if "roofline" in result:
        print(json.dumps(result["roofline"], indent=1))
    if result["status"].startswith("FAIL"):
        sys.exit(1)


if __name__ == "__main__":
    main()
