"""Serving launcher: a registered model at its published widths through the
paged engine (Pallas paged-attention decode on a TPU).

    PYTHONPATH=src python -m repro.launch.serve --arch mistral-nemo-12b --layers 8
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --smoke

``--layers N`` is the only depth cut: the layers left out stand for the
further stages of a pipeline. ``--smoke`` swaps in the family-preserving
CPU reduction (``smoke_config``). Prompts are seeded random token ids,
200 to 1,000 of them: two prefill buckets (512 and 1,024).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import Engine, Request, make_engine
from repro.sharding.axes import single_device_ctx

PROMPT_LEN = (200, 1000)


def build_config(arch: str, *, smoke: bool = False,
                 layers: int | None = None) -> ModelConfig:
    """The registered config, smoke-reduced on request, with only
    ``n_layers`` replaced when ``layers`` is given."""
    cfg = get_config(arch)
    if smoke:
        cfg = smoke_config(cfg)
    if layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg


def make_requests(cfg: ModelConfig, n: int, prompt_len: tuple[int, int],
                  max_new: int, seed: int) -> list[Request]:
    """``n`` requests with seeded uniform-random prompt lengths in
    ``[lo, hi]`` and token ids drawn over the whole vocabulary."""
    lo, hi = prompt_len
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(
                0, cfg.vocab, int(rng.integers(lo, hi + 1))).tolist(),
                    max_new=max_new)
            for i in range(n)]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="mistral-nemo-12b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the CPU-sized smoke reduction")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep only the first N layers (depth cut)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None) -> tuple[Engine, list[Request]]:
    """Serve seeded requests to completion; returns the engine and the
    finished requests so a caller can check them."""
    args = parse_args(argv)
    enable_compile_cache()
    cfg = build_config(args.arch, smoke=args.smoke, layers=args.layers)
    eng = make_engine(cfg, single_device_ctx(), seed=args.seed,
                      max_slots=args.max_slots, max_len=args.max_len,
                      paged=True, paged_kernel=True)
    reqs = make_requests(cfg, args.requests, PROMPT_LEN, args.max_new,
                         args.seed)
    t0 = time.perf_counter()
    eng.run(reqs)
    wall = time.perf_counter() - t0
    dev = jax.devices()[0]
    print(f"[serve] {cfg.name} ({cfg.n_layers} layers) on {dev.platform} "
          f"{dev.device_kind}: {len(reqs)} requests, "
          f"{sum(len(r.out) for r in reqs)} tokens, {wall:.2f} s wall "
          f"including compiles, {eng.prefill_compiles()} prefill compiles")
    for r in reqs[:4]:
        print(f"  req {r.rid}: prompt[{len(r.prompt)}] -> {r.out[:8]}...")
    return eng, reqs


if __name__ == "__main__":
    main()
