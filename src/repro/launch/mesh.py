"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

A FUNCTION, not a module constant — importing this module never touches jax
device state (smoke tests must keep seeing 1 device).
"""
from __future__ import annotations

from repro.sharding.axes import DEFAULT_RULES, ShardCtx, make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_ctx(*, multi_pod: bool = False, rules=None) -> ShardCtx:
    mesh = make_production_mesh(multi_pod=multi_pod)
    return ShardCtx(mesh=mesh, rules=dict(rules or DEFAULT_RULES))


# TPU v5e hardware constants (roofline denominators).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per-direction)
VMEM_BYTES = 16 * 2 ** 20
HBM_BYTES = 16 * 2 ** 30
