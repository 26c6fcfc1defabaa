"""Shared helpers of the benchmark's CPU tests: cells at smoke sizes.

The configurations under ``data/configs`` keep the served models' block
(GQA, rotary, SwiGLU, untied head; a sliding window for the Danube
stand-in) at ``repro.configs.smoke_config`` sizes, so the harness, the
engine and the reference run here in seconds.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import manifest  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E2E = ("ttft_p90_ms", "tpot_p90_ms", "tpot_p50_ms", "out_tok_s_per_chip",
       "setup_s")
PER_LAYER = ("setup.compile_s", "engine.prefill_share", "decode.step_ms",
             "step_mfu")


def smoke_registry(monkeypatch) -> None:
    """Serve the registry's smoke reductions (the tiny files' sizes): the
    model modules look the program's config up in ``repro.configs`` when
    they are called."""
    import repro.configs as registry
    real = registry.get_config
    monkeypatch.setattr(registry, "get_config",
                        lambda name: registry.smoke_config(real(name)))


def tiny_cell(config: str = "tiny-nemo", chips: int = 1,
              **overrides) -> manifest.Cell:
    conf = manifest.load_json(DATA / "configs" / f"{config}.json")
    conf.update(overrides)
    return manifest.Cell(
        name=f"{config}.chat", chips=chips, config_name=config, config=conf,
        traffic_name="tiny-chat", traffic=manifest.load_mix("tiny-chat", DATA),
        end_to_end=[{"name": n, "unit": "u"} for n in E2E],
        per_layer=[{"name": n, "unit": "u"} for n in PER_LAYER])


def run_tiny(monkeypatch, config: str = "tiny-nemo", seed: int = 1,
             seconds: float = 2.0, **overrides) -> dict:
    """A whole benchmark run of a tiny cell on the CPU, past the device
    check."""
    import jax
    from bench.harness import run_cell
    smoke_registry(monkeypatch)
    return run_cell(tiny_cell(config, **overrides), seed, seconds, False,
                    time.perf_counter(), PEAKS, jax.devices())
