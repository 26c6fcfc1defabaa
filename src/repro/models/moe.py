"""Mixture-of-Experts with expert parallelism over the ``model`` mesh axis.

Design (DESIGN.md §3): after attention, tokens are *replicated* across the
``model`` axis (Megatron-SP gathers the sequence), so each model-shard can
compute **only its local experts** for all of its tokens and the top-k
combine is a plain sum → one ``psum_scatter`` returns to the seq-sharded
residual. No all-to-all. Routing uses the standard sort → fixed per-expert
capacity buffers → batched matmul discipline (capacity-dropped tokens follow
Switch-Transformer semantics).

Expert weights are additionally FSDP-sharded over ``data`` on d_model and
all-gathered just-in-time inside the shard_map body (ZeRO-3).

The whole block is an explicit ``shard_map`` so every collective is chosen
by us, not GSPMD — this is the layer the §Perf iterations tune.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import gate_fn, is_gated, activation
from repro.sharding.axes import ShardCtx
from repro.sharding.params import pd

F32 = jnp.float32


def moe_defs(cfg: ModelConfig):
    m = cfg.moe
    E, F, D = m.n_experts, m.d_expert, cfg.d_model
    out_scale = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    d = {
        "router": pd((D, E), ("embed", None), dtype=jnp.float32),
        "w_up": pd((E, D, F), ("experts", "embed", None), dtype=cfg.pdtype),
        "w_down": pd((E, F, D), ("experts", None, "embed"), scale=out_scale,
                     dtype=cfg.pdtype),
    }
    if is_gated(cfg.act):
        d["w_gate"] = pd((E, D, F), ("experts", "embed", None), dtype=cfg.pdtype)
    if m.n_shared:
        Fs = m.n_shared * m.d_expert
        d["ws_up"] = pd((D, Fs), ("embed", "mlp"), dtype=cfg.pdtype)
        d["ws_down"] = pd((Fs, D), ("mlp", "embed"), scale=out_scale,
                          dtype=cfg.pdtype)
        if is_gated(cfg.act):
            d["ws_gate"] = pd((D, Fs), ("embed", "mlp"), dtype=cfg.pdtype)
    return d


def _gather_except(x, spec: P, keep=("model",)):
    """All-gather every sharded dim except mesh axes in `keep` (ZeRO-3)."""
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in reversed(axes):          # minor axis first → correct order
            if ax not in keep:
                x = jax.lax.all_gather(x, ax, axis=dim, tiled=True)
    return x


def moe_block(cfg: ModelConfig, p, x, ctx: ShardCtx):
    """x (B, S, D) seq-sharded → (out seq-sharded, router stats (2, E)).

    stats rows: [mean softmax prob per expert, fraction of slots per expert];
    combine into the aux loss with ``aux_loss_from_stats``.
    """
    m = cfg.moe
    E, k = m.n_experts, m.top_k
    msize = ctx.axis_size("model")
    assert E % msize == 0, (E, msize)
    E_loc = E // msize
    gated = is_gated(cfg.act)
    mesh = ctx.mesh
    bspec = ctx.spec(("batch", "seq", None), x.shape)
    pspecs = {n: ctx.spec(d.axes, d.shape)
              for n, d in _defs_meta(cfg).items()}

    def local(x_loc, params):
        midx = jax.lax.axis_index("model")
        xg = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)
        b, S, D = xg.shape
        T = b * S
        xf = xg.reshape(T, D)

        router = _gather_except(params["router"], pspecs["router"])
        w_up = _gather_except(params["w_up"], pspecs["w_up"])
        w_down = _gather_except(params["w_down"], pspecs["w_down"])

        logits = jnp.einsum("td,de->te", xf, router.astype(xf.dtype),
                            preferred_element_type=F32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, k)               # (T,k)
        gates = gates / jnp.sum(gates, -1, keepdims=True)

        # ---- stats for the aux loss (identical across the model axis)
        mean_prob = jnp.mean(probs, axis=0)                  # (E,)
        counts_all = jnp.bincount(eidx.reshape(-1), length=E)
        frac = counts_all.astype(F32) / (T * k)
        stats = jnp.stack([mean_prob, frac])[None]           # (1, 2, E)

        # ---- local dispatch: sort by local expert, capacity crop
        e0 = midx * E_loc
        flat_e = eidx.reshape(-1) - e0                       # (T*k,)
        is_local = (flat_e >= 0) & (flat_e < E_loc)
        key_e = jnp.where(is_local, flat_e, E_loc)           # sentinel last
        order = jnp.argsort(key_e, stable=True)
        sorted_e = key_e[order]
        counts = jnp.bincount(key_e, length=E_loc + 1)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(T * k) - offsets[sorted_e]
        Ce = max(1, math.ceil(T * k * m.capacity_factor / E))
        keep = (sorted_e < E_loc) & (pos < Ce)
        tok = order // k
        rows = jnp.where(keep, sorted_e * Ce + pos, E_loc * Ce)
        buf = jnp.zeros((E_loc * Ce + 1, D), xg.dtype)
        buf = buf.at[rows].set(xf[tok], mode="drop")
        buf = buf[:E_loc * Ce].reshape(E_loc, Ce, D)

        h = jnp.einsum("ecd,edf->ecf", buf, w_up)
        if gated:
            w_gate = _gather_except(params["w_gate"], pspecs["w_gate"])
            h = gate_fn(cfg.act)(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * h
        else:
            h = activation(cfg.act)(h)
        eo = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E_loc * Ce, D)
        eo = jnp.concatenate([eo, jnp.zeros((1, D), eo.dtype)], axis=0)

        slot = eo[rows] * (gates.reshape(-1)[order] * keep)[:, None].astype(eo.dtype)
        out = jnp.zeros((T, D), eo.dtype).at[tok].add(slot)

        # ---- shared experts fold into the same psum_scatter
        if m.n_shared:
            ws_up = params["ws_up"]          # (D, Fs/msize) local slice
            ws_up = _gather_except(ws_up, pspecs["ws_up"])
            ws_down = _gather_except(params["ws_down"], pspecs["ws_down"])
            hs = jnp.einsum("td,df->tf", xf, ws_up)
            if gated:
                ws_gate = _gather_except(params["ws_gate"], pspecs["ws_gate"])
                hs = gate_fn(cfg.act)(jnp.einsum("td,df->tf", xf, ws_gate)) * hs
            else:
                hs = activation(cfg.act)(hs)
            out = out + jnp.einsum("tf,fd->td", hs, ws_down)

        out = out.reshape(b, S, D)
        out = jax.lax.psum_scatter(out, "model", scatter_dimension=1,
                                   tiled=True)
        return out, stats

    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    stats_spec = P(dp if dp else None, None, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(bspec, {n: pspecs[n] for n in p}),
                       out_specs=(bspec, stats_spec),
                       check_vma=False)
    out, stats = fn(x, dict(p))
    return out, jnp.mean(stats, axis=0)


def moe_decode(cfg: ModelConfig, p, x, ctx: ShardCtx):
    """Decode-path MoE: x (B, D) batch-sharded, no sequence to scatter over —
    each model-shard computes its local experts for its batch rows, combine
    is a plain psum over ``model``."""
    m = cfg.moe
    E, k = m.n_experts, m.top_k
    msize = ctx.axis_size("model")
    E_loc = E // msize
    gated = is_gated(cfg.act)
    mesh = ctx.mesh
    xspec = ctx.spec(("batch", None), x.shape)
    pspecs = {n: ctx.spec(d.axes, d.shape)
              for n, d in _defs_meta(cfg).items()}

    def local(xf, params):
        midx = jax.lax.axis_index("model")
        T, D = xf.shape
        router = _gather_except(params["router"], pspecs["router"])
        w_up = _gather_except(params["w_up"], pspecs["w_up"])
        w_down = _gather_except(params["w_down"], pspecs["w_down"])
        logits = jnp.einsum("td,de->te", xf, router.astype(xf.dtype),
                            preferred_element_type=F32)
        gates, eidx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
        gates = gates / jnp.sum(gates, -1, keepdims=True)
        e0 = midx * E_loc
        # decode batches are small: dense per-local-expert masked compute
        out = jnp.zeros((T, D), xf.dtype)
        onehot = jax.nn.one_hot(eidx - e0, E_loc, dtype=F32)      # (T,k,E_loc)
        w_tok = jnp.einsum("tke,tk->te", onehot, gates)           # (T,E_loc)
        for el in range(E_loc):
            h = jnp.einsum("td,df->tf", xf, w_up[el])
            if gated:
                w_gate = _gather_except(params["w_gate"], pspecs["w_gate"])
                h = gate_fn(cfg.act)(jnp.einsum("td,df->tf", xf,
                                                w_gate[el])) * h
            else:
                h = activation(cfg.act)(h)
            o = jnp.einsum("tf,fd->td", h, w_down[el])
            out = out + o * w_tok[:, el:el + 1].astype(o.dtype)
        if m.n_shared:
            ws_up = _gather_except(params["ws_up"], pspecs["ws_up"])
            ws_down = _gather_except(params["ws_down"], pspecs["ws_down"])
            hs = jnp.einsum("td,df->tf", xf, ws_up)
            if gated:
                ws_gate = _gather_except(params["ws_gate"], pspecs["ws_gate"])
                hs = gate_fn(cfg.act)(jnp.einsum("td,df->tf", xf, ws_gate)) * hs
            else:
                hs = activation(cfg.act)(hs)
            out = out + jnp.einsum("tf,fd->td", hs, ws_down)
        return jax.lax.psum(out, "model")

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(xspec, {n: pspecs[n] for n in p}),
                       out_specs=xspec, check_vma=False)
    return fn(x, dict(p))


def _defs_meta(cfg):
    return moe_defs(cfg)


def aux_loss_from_stats(cfg: ModelConfig, stats) -> jax.Array:
    """stats (2, E) or summed over layers (n, 2, E)."""
    m = cfg.moe
    if stats.ndim == 3:
        stats = jnp.mean(stats, axis=0)
    mean_prob, frac = stats[0], jax.lax.stop_gradient(stats[1])
    return m.aux_weight * m.n_experts * jnp.sum(mean_prob * frac)
