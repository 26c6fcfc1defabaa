"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch mistral-nemo-12b \
        --steps 50 --batch 8 --seq 128 [--smoke] [--microbatches 2] \
        [--compression int8] [--ckpt-dir ckpt]

Without ``--smoke`` the registered config trains at its published widths;
``--smoke`` swaps in the family-preserving CPU reduction.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, smoke_config
from repro.data.loader import PrefetchLoader
from repro.data.synthetic import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.sharding.axes import single_device_ctx
from repro.train.compression import CompressionConfig
from repro.train.loop import LoopConfig, train_loop
from repro.train.optimizer import OptConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--smoke", action="store_true",
                    help="train the CPU-sized smoke reduction")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", choices=["none", "int8", "topk"],
                    default="none")
    ap.add_argument("--moments", choices=["float32", "int8"],
                    default="float32")
    ap.add_argument("--ckpt-dir", default="ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    ctx = single_device_ctx()
    ocfg = OptConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                     decay_steps=args.steps, moments_dtype=args.moments)
    lcfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir)
    ccfg = CompressionConfig(kind=args.compression)
    data = SyntheticLM(cfg.vocab, args.seq, seed=args.seed)
    loader = PrefetchLoader(data.iterator(args.batch), ctx)

    def log(step, row):
        if step % max(1, args.steps // 20) == 0:
            print(f"step {step:5d} loss {row['loss']:.4f} "
                  f"|g| {row['grad_norm']:.3f} lr {row['lr']:.2e} "
                  f"{row['tokens'] / row['dt']:.0f} tok/s")

    res = train_loop(cfg, ocfg, lcfg, ctx, iter(loader), ccfg=ccfg,
                     on_step=log, seed=args.seed)
    print(f"done: {len(res.history)} steps, restarts={res.restarts}, "
          f"resumed_from={res.resumed_from}, "
          f"final loss {res.history[-1]['loss']:.4f}")
    loader.close()


if __name__ == "__main__":
    main()
