"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 100 --batch 8 --seq 64

It runs on every device JAX sees unless --devices says fewer. On a pod
this process runs per host (jax.distributed.initialize is called when
JAX_COORDINATOR is set). With JAX_PLATFORMS=cpu, --devices N makes N
virtual host devices. Arch/shape/parallelism knobs mirror the dry-run's.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--tp", type=int, default=None)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--backend", default="microcode")
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--compress", default="")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices and os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")
    if os.environ.get("JAX_COORDINATOR"):
        import jax
        jax.distributed.initialize()  # multi-host pod entry point

    from repro.configs import get_config, reduced_config
    from repro.configs.base import ParallelConfig
    from repro.data import DataConfig
    from repro.launch import configure_compile_cache, make_mesh_for
    from repro.optim import adamw
    from repro.optim.schedules import cosine_warmup
    from repro.runtime import Trainer, TrainerConfig

    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    mesh = make_mesh_for(args.devices, tp=args.tp)
    pcfg = ParallelConfig(backend=args.backend, sequence_parallel=args.sp,
                          remat=args.remat,
                          grad_compression=args.compress or None)
    trainer = Trainer(
        cfg, pcfg, mesh, adamw.AdamWConfig(lr=args.lr),
        DataConfig(global_batch=args.batch, seq_len=args.seq,
                   seed=args.seed),
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                      ckpt_every=args.ckpt_every),
        lr_schedule=lambda s: cosine_warmup(s, 20, args.steps))
    log = trainer.run()
    for rec in log:
        if "step" in rec and rec["step"] % 10 == 0:
            print(f"step {rec['step']:5d}  ce {rec['ce_mean']:.4f}  "
                  f"{rec['dt']*1e3:.0f} ms")
    if trainer.watchdog.events:
        print("straggler events:", trainer.watchdog.events)


if __name__ == "__main__":
    main()
