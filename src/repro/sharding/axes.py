"""Logical-axis → mesh-axis sharding rules (MaxText-style).

Every parameter / activation dimension carries a *logical* axis name; a rule
table maps logical names to mesh axes. Rules silently drop a mesh axis when
the dimension size is not divisible by the mesh-axis size (e.g. 8 KV heads on
a 16-way ``model`` axis → replicated), which keeps one rule table valid for
all 10 architectures.

All model code threads a :class:`ShardCtx` (mesh + rules) explicitly; with a
single-device mesh every constraint is a no-op, so the same code path runs in
CPU smoke tests and in the 512-chip dry-run.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Logical axis vocabulary (see DESIGN.md §3):
#   batch     global batch                     → (pod, data)
#   seq       sequence (residual stream, SP)   → model
#   kv_seq    decode KV-cache sequence         → model   (flash-decoding)
#   embed     d_model (params; FSDP)           → data   [+ pod for huge models]
#   vocab     vocabulary                       → model
#   heads     query heads                      → model
#   kv_heads  kv heads                         → model (if divisible)
#   mlp       ffn hidden                       → model
#   experts   MoE expert axis                  → model (EP)
#   d_inner   mamba inner channels             → model
#   layers    stacked scan axis                → None
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": ("model",),
    "kv_seq": ("model",),
    "embed": ("data",),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "qk": (),
    "v": (),
    "mlp": ("model",),
    "experts": ("model",),
    "layers": (),
    "d_inner": ("model",),
    "ssm_state": (),
    "ssm_heads": ("model",),
    "conv": (),
    "lora": (),
    "frontend": (),
    "null": (),
}

# For very large models (≳100 B params) optimizer state must shard over the
# pod axis too, otherwise a 16 GB v5e chip cannot hold its slice.
ZERO_POD_RULES = dict(DEFAULT_RULES, embed=("pod", "data"), experts=("model",))


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh + rule table threaded through all model code."""
    mesh: Mesh
    rules: Mapping[str, tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES))

    @property
    def model_axis(self) -> str:
        return "model"

    @property
    def data_axes(self) -> tuple[str, ...]:
        return tuple(a for a in ("pod", "data") if a in self.mesh.shape)

    def axis_size(self, name: str) -> int:
        return self.mesh.shape.get(name, 1)

    def spec(self, axes: Sequence[str | None], shape: Sequence[int]) -> P:
        return logical_to_spec(axes, shape, self.mesh, self.rules)

    def sharding(self, axes: Sequence[str | None], shape: Sequence[int]) -> NamedSharding:
        if self.mesh.size == 1:
            # the spec a jitted step emits on one device: arrays placed
            # here and a step's outputs then share one compile
            return NamedSharding(self.mesh, P())
        return NamedSharding(self.mesh, self.spec(axes, shape))

    def constrain(self, x: jax.Array, axes: Sequence[str | None]) -> jax.Array:
        if self.mesh.empty or self.mesh.size == 1:
            return x
        return jax.lax.with_sharding_constraint(x, self.sharding(axes, x.shape))


def make_mesh(shape: Sequence[int], names: Sequence[str],
              devices=None) -> Mesh:
    """The one place meshes are built. Axes are ``Auto`` so GSPMD
    propagates shardings and ``ShardCtx.constrain`` may use
    ``with_sharding_constraint`` (``jax.make_mesh`` defaults to
    ``Explicit`` axes, which reject both)."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def single_device_ctx(device=None) -> ShardCtx:
    """1-device mesh with the production axis names on ``device`` (default
    the first device) — one serving replica or a smoke test."""
    device = device if device is not None else jax.devices()[0]
    return ShardCtx(mesh=make_mesh((1, 1), ("data", "model"), [device]))


def mesh_axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    return math.prod(mesh.shape.get(n, 1) for n in names)


def logical_to_spec(
    axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Mapping[str, tuple[str, ...]] | None = None,
) -> P:
    """Map logical axis names to a PartitionSpec, dropping non-divisible axes."""
    rules = rules or DEFAULT_RULES
    spec: list = []
    used: set[str] = set()
    for dim, name in zip(shape, axes):
        if name is None:
            spec.append(None)
            continue
        mesh_axes = [a for a in rules.get(name, ()) if a in mesh.shape and a not in used]
        # keep the largest divisible prefix of the rule's mesh axes
        keep: list[str] = []
        prod = 1
        for a in mesh_axes:
            if mesh.shape[a] > 1 and dim % (prod * mesh.shape[a]) == 0:
                keep.append(a)
                prod *= mesh.shape[a]
            elif mesh.shape[a] == 1:
                continue
            else:
                break
        used.update(keep)
        if not keep:
            spec.append(None)
        elif len(keep) == 1:
            spec.append(keep[0])
        else:
            spec.append(tuple(keep))
    return P(*spec)


def named_sharding(
    axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Mapping[str, tuple[str, ...]] | None = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(axes, shape, mesh, rules))
