"""Pipeline parallelism (GPipe-style) over a ``stage`` mesh axis.

Each stage owns a contiguous group of layers; microbatches stream through a
`collective_permute` ring inside ``shard_map``. The schedule is the classic
(S + M - 1)-tick loop: at tick t, stage s computes microbatch (t - s) if it
is in range, then passes activations to stage s+1. Bubble fraction =
(S-1)/(S+M-1), reported by :func:`bubble_fraction`.

The production dry-run meshes use DP×TP(×EP/SP) — the assigned shapes don't
need PP — but the mechanism is exercised end-to-end (loss matches the
unpipelined reference bit-for-bit modulo reduction order) by
``tests/test_pipeline.py`` on a 4-stage host mesh, and composes with the
other axes (the stage shard_map is just another mesh dim).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_stages + n_microbatches - 1)


def pipeline_apply(mesh: Mesh, layer_fn, params_stacked, x_mb, *,
                   axis: str = "stage"):
    """Run ``layer_fn(params_i, h)`` for each of S stages over M microbatches.

    params_stacked: pytree with leading axis S (stage-major layer groups),
    sharded over `axis`. x_mb: (M, mb, …) microbatched input, replicated.
    Returns (M, mb, …) outputs after all S stages.
    """
    S = mesh.shape[axis]
    M = x_mb.shape[0]

    def local(params_loc, x_loc):
        # params_loc: (1, …) this stage's layer group; x_loc: (M, mb, …)
        sidx = jax.lax.axis_index(axis)
        p_stage = jax.tree.map(lambda x: x[0], params_loc)
        mb_shape = x_loc.shape[1:]
        buf = jnp.zeros(mb_shape, x_loc.dtype)      # activation in flight
        out = jnp.zeros_like(x_loc)
        perm_fwd = [(i, (i + 1) % S) for i in range(S)]

        def tick(t, carry):
            buf, out = carry
            mb_idx = t - sidx                       # microbatch at this stage
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 ingests a fresh microbatch; others use the ring buffer
            fresh = jax.lax.dynamic_index_in_dim(
                x_loc, jnp.clip(mb_idx, 0, M - 1), 0, keepdims=False)
            h_in = jnp.where(sidx == 0, fresh, buf)
            h_out = layer_fn(p_stage, h_in)
            h_out = jnp.where(active, h_out, jnp.zeros_like(h_out))
            # last stage writes its finished microbatch
            write_idx = jnp.clip(mb_idx, 0, M - 1)
            do_write = active & (sidx == S - 1)
            cur = jax.lax.dynamic_index_in_dim(out, write_idx, 0,
                                               keepdims=False)
            upd = jnp.where(do_write, h_out, cur)
            out = jax.lax.dynamic_update_index_in_dim(out, upd, write_idx, 0)
            # ring-shift activations to the next stage
            buf = jax.lax.ppermute(h_out, axis, perm_fwd)
            return (buf, out)

        buf, out = jax.lax.fori_loop(0, S + M - 1, tick, (buf, out))
        # `out` only valid on the last stage → broadcast it to all stages
        out = jax.lax.psum(
            jnp.where(sidx == S - 1, out, jnp.zeros_like(out)), axis)
        return out

    pspec = jax.tree.map(lambda _: P(axis), params_stacked)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P(),
                       check_vma=False)
    return fn(params_stacked, x_mb)
