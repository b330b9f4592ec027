"""Serving launcher: prefill + greedy decode over the sharded caches.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --prompt-len 16 --gen 8

It serves on every device JAX sees unless --devices says fewer; with
JAX_PLATFORMS=cpu, --devices N makes N virtual host devices. --full
serves the published widths instead of the reduced config.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--tp", type=int, default=None)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--backend", default="microcode")
    args = ap.parse_args()

    if args.devices and os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, reduced_config
    from repro.configs.base import ParallelConfig
    from repro.launch import configure_compile_cache, make_mesh_for
    from repro.parallel import stages

    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    mesh = make_mesh_for(args.devices, tp=args.tp)
    tp = mesh.shape["model"]
    pcfg = ParallelConfig(backend=args.backend,
                          moe_capacity_factor=8.0)
    s_max = args.prompt_len + args.gen
    params = stages.init_params(cfg, mesh, tp, seed=0)
    dstep, _, _, _ = stages.build_decode_step(
        cfg, pcfg, mesh, s_max=s_max, global_batch=args.batch)
    cache = stages.init_cache(cfg, pcfg, mesh, tp, args.batch, s_max)

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size,
                          (args.batch, args.prompt_len)).astype(np.int32)
    # teacher-forced prompt consumption, then free-running generation
    # (decode-only path exercises the same program serving uses per token)
    seqs = [prompt[:, i] for i in range(args.prompt_len)]
    tok = jnp.asarray(prompt[:, :1])
    for t in range(args.prompt_len + args.gen - 1):
        nxt, cache = dstep(params, cache, tok, jnp.int32(t))
        if t + 1 < args.prompt_len:
            tok = jnp.asarray(prompt[:, t + 1:t + 2])
        else:
            seqs.append(np.asarray(nxt))
            tok = nxt[:, None].astype(jnp.int32)
    out = np.stack(seqs, axis=1)
    print("generated (batch x tokens):")
    print(out)


if __name__ == "__main__":
    main()
