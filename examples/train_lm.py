"""End-to-end LM training driver: ~100M-param model, a few hundred steps.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/train_lm.py --steps 200

Uses the full production stack — shard_map train step over a (data, model)
mesh, ACCL-X collectives (streaming TP + ZeRO-1 ring reduce-scatter), the
synthetic data pipeline, async checkpointing, the straggler watchdog and
preemption drain — on a mamba2-130m-family model scaled to fit the CPU run.
"""
import argparse
import dataclasses
import tempfile

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, get_smoke_config
from repro.core.config import CommConfig, OVERLAPPED_CONFIG
from repro.data.pipeline import DataConfig
from repro.launch import compile_cache, setup
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.train import loop as loop_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--full-size", action="store_true",
                    help="use the real config (defaults to a ~100M-scale "
                    "reduction that trains quickly on CPU)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--comm", default="fused",
                    choices=("fused", "overlapped", "auto"),
                    help="TP/MoE comm path: fused (one psum per combine), "
                    "overlapped (chunked double-buffered TP reduce + chunked "
                    "MoE all-to-all), or auto (fastest measured TuneDB config)")
    args = ap.parse_args()

    if args.full_size:
        cfg = get_config(args.arch)
    else:
        cfg = get_config(args.arch)
        # ~100M-param variant of the same family, CPU-trainable
        cfg = dataclasses.replace(
            cfg, n_layers=min(cfg.n_layers, 6),
            d_model=min(cfg.d_model, 512),
            d_ff=min(cfg.d_ff, 1024) if cfg.d_ff else 0,
            vocab_size=min(cfg.vocab_size, 8192),
            ssm_chunk=min(cfg.ssm_chunk, 32) if cfg.ssm_chunk else 0,
            dtype=jnp.float32, remat=False)

    n = jax.device_count()
    model_axis = 2 if n >= 4 else 1
    mesh = make_mesh((n // model_axis, model_axis), ("data", "model"))
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.0f}M "
          f"mesh=({n//model_axis}x{model_axis})")

    oc = adamw.OptConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps,
                         zero1=True)
    comm = {"fused": CommConfig(), "overlapped": OVERLAPPED_CONFIG,
            "auto": "auto"}[args.comm]
    sess = setup.build_session(cfg, mesh, comm, oc=oc)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    history = loop_mod.train(
        sess, data_cfg,
        loop_mod.LoopConfig(n_steps=args.steps, ckpt_every=max(args.steps // 2, 1),
                            ckpt_dir=ckpt_dir, log_every=10))
    print(f"\nloss: {history[0]:.3f} -> {history[-1]:.3f} "
          f"({len(history)} steps); checkpoints in {ckpt_dir}")
    assert history[-1] < history[0], "loss should decrease"


if __name__ == "__main__":
    compile_cache.configure()
    main()
