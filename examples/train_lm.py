"""End-to-end training driver: a ~100M-param LM for a few hundred steps.

Uses the full production stack — data pipeline, AdamW, checkpointing,
fault-tolerant trainer, collective engine for every collective — on the
8-virtual-device simulation mesh. The config is smollm-360m narrowed to
~100M params (depth/width cut, real vocab).

  JAX_PLATFORMS=cpu python examples/train_lm.py --steps 300
"""
import os

if os.environ.get("JAX_PLATFORMS") == "cpu":  # 8 virtual host devices
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import argparse  # noqa: E402
import dataclasses  # noqa: E402

from repro.launch import configure_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ParallelConfig  # noqa: E402
from repro.core.topology import make_mesh  # noqa: E402
from repro.data import DataConfig  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.optim.schedules import cosine_warmup  # noqa: E402
from repro.runtime import Trainer, TrainerConfig  # noqa: E402


def lm_100m():
    base = get_config("smollm-360m")
    return dataclasses.replace(
        base, n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
        head_dim=64, d_ff=2048, vocab_size=49152,
        param_dtype="float32", compute_dtype="float32")


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--backend", default="microcode",
                    choices=("microcode", "native"))
    ap.add_argument("--compress", default="", choices=("", "int8", "bf16"))
    args = ap.parse_args()

    cfg = lm_100m()
    print(f"params: {cfg.n_params()/1e6:.1f}M")
    mesh = make_mesh((1, 4, 2), ("pod", "data", "model"))
    pcfg = ParallelConfig(backend=args.backend, remat="none",
                          grad_compression=args.compress or None)
    trainer = Trainer(
        cfg, pcfg, mesh,
        adamw.AdamWConfig(lr=3e-4, weight_decay=0.01),
        DataConfig(global_batch=args.batch, seq_len=args.seq, seed=0),
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                      ckpt_every=100, log_every=20),
        lr_schedule=lambda s: cosine_warmup(s, 50, args.steps))
    log = trainer.run()
    for rec in log:
        if "step" in rec and rec["step"] % 20 == 0:
            print(f"step {rec['step']:4d}  ce {rec['ce_mean']:.4f}  "
                  f"gnorm {rec['grad_norm']:.3f}  {rec['dt']*1e3:.0f} ms")
    final = [r for r in log if "step" in r][-1]
    print(f"final: step {final['step']} ce {final['ce_mean']:.4f}")
    assert final["ce_mean"] < log[0]["ce_mean"], "training did not improve"


if __name__ == "__main__":
    main()
