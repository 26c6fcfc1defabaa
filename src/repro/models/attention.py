"""Attention: GQA / MLA / sliding-window, with a chunked online-softmax core.

The XLA training/prefill path (``attend_chunked``) scans over the *block
pairs* (q-chunk, kv-chunk) that the mask actually allows — causal masks cost
~T²/2 and sliding windows cost O(T·w) — carrying flash-style (o, m, l)
accumulators. Memory is O(T·d) (no T×T score tensor), so the 32 k-prefill
cells compile and fit. The Pallas flash-attention kernel
(``repro.kernels.flash_attention``) is the TPU-optimised equivalent,
validated against the same reference.

Sharding: q heads over ``model``; kv heads over ``model`` iff divisible
(else replicated — cheap for GQA); sequence gathered at entry, output
reduce-scattered back to the seq-sharded residual (Megatron SP).
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, rmsnorm, rmsnorm_def, rope_tables
from repro.sharding.axes import ShardCtx
from repro.sharding.params import pd

F32 = jnp.float32
NEG = -1e30


# ------------------------------------------------------------- block pairs
def block_pairs(Tq: int, Tk: int, qc: int, kc: int, *, causal: bool,
                window: int, q_offset: int = 0) -> np.ndarray:
    """Static (P, 2) int32 array of (q_chunk, kv_chunk) indices that contain
    at least one unmasked (i, j) position."""
    nq, nk = -(-Tq // qc), -(-Tk // kc)
    pairs = []
    for qi in range(nq):
        q0 = qi * qc + q_offset          # global position of first query row
        q1 = min(qi * qc + qc, Tq) - 1 + q_offset
        for kj in range(nk):
            k0 = kj * kc
            k1 = min(kj * kc + kc, Tk) - 1
            if causal and k0 > q1:
                continue
            if window and k1 <= q0 - window:
                continue
            pairs.append((qi, kj))
    assert pairs, "empty attention mask"
    return np.asarray(pairs, dtype=np.int32)


def _mask_block(qs, ks, qc, kc, *, causal, window, q_offset, Tq, Tk, Tqp,
                Tkp, kv_offset=0):
    iq = qs + jnp.arange(qc) + q_offset
    jk = ks + jnp.arange(kc) + kv_offset
    ok = jnp.ones((qc, kc), bool)
    if causal:
        ok &= jk[None, :] <= iq[:, None]
    if window:
        ok &= jk[None, :] > iq[:, None] - window
    if not isinstance(kv_offset, int) or kv_offset != 0:
        ok &= jk[None, :] >= 0          # neighbor-exchange boundary shards
    if Tq != Tqp or Tk != Tkp:  # padding rows/cols
        ok &= (iq[:, None] - q_offset < Tq) & \
              (jk[None, :] - kv_offset < Tk)
    return ok


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _attend_core(q, k, v, scale: float, causal: bool, window: int,
                 softcap: float, q_chunk: int, kv_chunk: int, q_offset: int):
    out, _ = _attend_fwd(q, k, v, scale, causal, window, softcap, q_chunk,
                         kv_chunk, q_offset)
    return out


def attend_chunked(q, k, v, *, scale: float, causal: bool = True,
                   window: int = 0, softcap: float = 0.0, q_chunk: int = 512,
                   kv_chunk: int = 512, q_offset=0):
    """Flash attention, XLA path (O(T·d) memory in fwd AND bwd).

    q (B,Tq,Hkv,G,dh), k (B,Tk,Hkv,dh), v (B,Tk,Hkv,dv) → (B,Tq,Hkv,G,dv).
    G = query-group size (GQA); pass G=1 slices for MHA/MLA.

    Static ``q_offset`` (head-parallel path): custom-VJP flash backward, and
    only the block pairs the mask allows are scanned (causal ≈ T²/2).
    Traced ``q_offset`` (context-parallel path, per-shard offset): plain
    AD-through-scan over the full block rectangle with traced masks — the
    CP shard's q is 1/msize of the sequence, so the scan carry stays small.
    """
    if isinstance(q_offset, (int, np.integer)):
        return _attend_core(q, k, v, scale, causal, window, softcap, q_chunk,
                            kv_chunk, int(q_offset))
    return _attend_scan(q, k, v, scale=scale, causal=causal, window=window,
                        softcap=softcap, q_chunk=q_chunk, kv_chunk=kv_chunk,
                        q_offset=q_offset)


def _attend_scan(q, k, v, *, scale, causal, window, softcap, q_chunk,
                 kv_chunk, q_offset, kv_offset=0):
    """Differentiable-through-scan variant accepting traced q/kv offsets."""
    B, Tq, Hkv, G, dh = q.shape
    Tk, dv = k.shape[1], v.shape[-1]
    qc, kc = min(q_chunk, Tq), min(kv_chunk, Tk)
    qp, kp, vp = _pad_qkv(q, k, v, qc, kc)
    Tqp, Tkp = qp.shape[1], kp.shape[1]
    pairs = jnp.asarray(
        [(i, j) for i in range(Tqp // qc) for j in range(Tkp // kc)],
        jnp.int32)

    o0 = jnp.zeros((B, Tqp, Hkv, G, dv), F32)
    m0 = jnp.full((B, Tqp, Hkv, G), NEG, F32)
    l0 = jnp.zeros((B, Tqp, Hkv, G), F32)

    def block(q_blk, k_blk, v_blk, o_old, m_old, l_old, qs, ks):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk.astype(F32) * scale,
                       k_blk.astype(F32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        ok = _mask_block(qs, ks, qc, kc, causal=causal, window=window,
                         q_offset=q_offset, Tq=Tq, Tk=Tk, Tqp=Tqp, Tkp=Tkp,
                         kv_offset=kv_offset)
        s = jnp.where(ok[None, None, None], s, NEG)
        m_blk = jnp.moveaxis(jnp.max(s, axis=-1), -1, 1)
        m_new = jnp.maximum(m_old, m_blk)
        m_safe = jnp.where(m_new <= NEG / 2, 0.0, m_new)
        p = jnp.exp(s - jnp.moveaxis(m_safe, 1, -1)[..., None])
        p = jnp.where(ok[None, None, None], p, 0.0)
        corr = jnp.exp(jnp.where(m_old <= NEG / 2, NEG, m_old) - m_safe)
        o_new = (o_old * corr[..., None]
                 + jnp.einsum("bhgqk,bkhd->bqhgd", p, v_blk.astype(F32)))
        l_new = l_old * corr + jnp.moveaxis(jnp.sum(p, -1), -1, 1)
        return o_new, m_new, l_new

    block = jax.checkpoint(block, prevent_cse=False)

    def body(carry, pair):
        o, m, l = carry
        qs, ks = pair[0] * qc, pair[1] * kc
        args = [jax.lax.dynamic_slice_in_dim(t, qs, qc, 1)
                for t in (qp,)] + \
               [jax.lax.dynamic_slice_in_dim(t, ks, kc, 1) for t in (kp, vp)]
        o_old = jax.lax.dynamic_slice_in_dim(o, qs, qc, 1)
        m_old = jax.lax.dynamic_slice_in_dim(m, qs, qc, 1)
        l_old = jax.lax.dynamic_slice_in_dim(l, qs, qc, 1)
        o_new, m_new, l_new = block(args[0], args[1], args[2], o_old, m_old,
                                    l_old, qs, ks)
        o = jax.lax.dynamic_update_slice_in_dim(o, o_new, qs, 1)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, qs, 1)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, qs, 1)
        return (o, m, l), None

    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), pairs)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out[:, :Tq].astype(q.dtype)


def _pad_qkv(q, k, v, qc, kc):
    Tq, Tk = q.shape[1], k.shape[1]
    pq, pk = (-Tq) % qc, (-Tk) % kc
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq)) + ((0, 0),) * (q.ndim - 2))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    return q, k, v


def _attend_fwd(q, k, v, scale, causal, window, softcap, q_chunk, kv_chunk,
                q_offset):
    B, Tq, Hkv, G, dh = q.shape
    Tk, dv = k.shape[1], v.shape[-1]
    qc, kc = min(q_chunk, Tq), min(kv_chunk, Tk)
    qp, kp, vp = _pad_qkv(q, k, v, qc, kc)
    Tqp, Tkp = qp.shape[1], kp.shape[1]
    pairs = jnp.asarray(block_pairs(Tq, Tk, qc, kc, causal=causal,
                                    window=window, q_offset=q_offset))

    o0 = jnp.zeros((B, Tqp, Hkv, G, dv), F32)
    m0 = jnp.full((B, Tqp, Hkv, G), NEG, F32)
    l0 = jnp.zeros((B, Tqp, Hkv, G), F32)

    def body(carry, pair):
        o, m, l = carry
        qs, ks = pair[0] * qc, pair[1] * kc
        q_blk = jax.lax.dynamic_slice_in_dim(qp, qs, qc, 1).astype(F32)
        k_blk = jax.lax.dynamic_slice_in_dim(kp, ks, kc, 1).astype(F32)
        v_blk = jax.lax.dynamic_slice_in_dim(vp, ks, kc, 1).astype(F32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk * scale, k_blk)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        ok = _mask_block(qs, ks, qc, kc, causal=causal, window=window,
                         q_offset=q_offset, Tq=Tq, Tk=Tk, Tqp=Tqp, Tkp=Tkp)
        s = jnp.where(ok[None, None, None], s, NEG)
        m_old = jax.lax.dynamic_slice_in_dim(m, qs, qc, 1)
        l_old = jax.lax.dynamic_slice_in_dim(l, qs, qc, 1)
        o_old = jax.lax.dynamic_slice_in_dim(o, qs, qc, 1)
        m_blk = jnp.moveaxis(jnp.max(s, axis=-1), -1, 1)     # (B,qc,h,g)
        m_new = jnp.maximum(m_old, m_blk)
        m_safe = jnp.where(m_new <= NEG / 2, 0.0, m_new)
        p = jnp.exp(s - jnp.moveaxis(m_safe, 1, -1)[..., None])
        p = jnp.where(ok[None, None, None], p, 0.0)
        corr = jnp.exp(jnp.where(m_old <= NEG / 2, NEG, m_old) - m_safe)
        o_new = (o_old * corr[..., None]
                 + jnp.einsum("bhgqk,bkhd->bqhgd", p, v_blk))
        l_new = l_old * corr + jnp.moveaxis(jnp.sum(p, -1), -1, 1)
        o = jax.lax.dynamic_update_slice_in_dim(o, o_new, qs, 1)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, qs, 1)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, qs, 1)
        return (o, m, l), None

    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), pairs)
    lsafe = jnp.maximum(l, 1e-30)
    out = (o / lsafe[..., None])[:, :Tq].astype(q.dtype)
    lse = (jnp.where(m <= NEG / 2, 0.0, m) + jnp.log(lsafe))[:, :Tq]
    return out, (q, k, v, out, lse)


def _attend_bwd(scale, causal, window, softcap, q_chunk, kv_chunk, q_offset,
                res, do):
    """Flash backward: recompute p per block from saved lse; plain scans —
    nothing accumulated across AD, so memory stays O(T·d)."""
    q, k, v, out, lse = res
    B, Tq, Hkv, G, dh = q.shape
    Tk, dv = k.shape[1], v.shape[-1]
    qc, kc = min(q_chunk, Tq), min(kv_chunk, Tk)
    qp, kp, vp = _pad_qkv(q, k, v, qc, kc)
    Tqp, Tkp = qp.shape[1], kp.shape[1]
    dop = jnp.pad(do.astype(F32),
                  ((0, 0), (0, Tqp - Tq), (0, 0), (0, 0), (0, 0)))
    lsep = jnp.pad(lse, ((0, 0), (0, Tqp - Tq), (0, 0), (0, 0)))
    # delta_i = rowsum(do ⊙ o)
    delta = jnp.sum(dop[:, :Tq] * out.astype(F32), axis=-1)
    delta = jnp.pad(delta, ((0, 0), (0, Tqp - Tq), (0, 0), (0, 0)))
    pairs = jnp.asarray(block_pairs(Tq, Tk, qc, kc, causal=causal,
                                    window=window, q_offset=q_offset))

    dq0 = jnp.zeros((B, Tqp, Hkv, G, dh), F32)
    dk0 = jnp.zeros((B, Tkp, Hkv, dh), F32)
    dv0 = jnp.zeros((B, Tkp, Hkv, dv), F32)

    def body(carry, pair):
        dq, dk, dv_ = carry
        qs, ks = pair[0] * qc, pair[1] * kc
        q_blk = jax.lax.dynamic_slice_in_dim(qp, qs, qc, 1).astype(F32)
        k_blk = jax.lax.dynamic_slice_in_dim(kp, ks, kc, 1).astype(F32)
        v_blk = jax.lax.dynamic_slice_in_dim(vp, ks, kc, 1).astype(F32)
        do_blk = jax.lax.dynamic_slice_in_dim(dop, qs, qc, 1)
        lse_blk = jax.lax.dynamic_slice_in_dim(lsep, qs, qc, 1)
        dl_blk = jax.lax.dynamic_slice_in_dim(delta, qs, qc, 1)
        s_pre = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk * scale, k_blk)
        if softcap:
            t = jnp.tanh(s_pre / softcap)
            s = t * softcap
        else:
            s = s_pre
        ok = _mask_block(qs, ks, qc, kc, causal=causal, window=window,
                         q_offset=q_offset, Tq=Tq, Tk=Tk, Tqp=Tqp, Tkp=Tkp)
        s = jnp.where(ok[None, None, None], s, NEG)
        p = jnp.exp(s - jnp.moveaxis(lse_blk, 1, -1)[..., None])
        p = jnp.where(ok[None, None, None], p, 0.0)
        dv_blk = jnp.einsum("bhgqk,bqhgd->bkhd", p, do_blk)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_blk, v_blk)
        ds = p * (dp - jnp.moveaxis(dl_blk, 1, -1)[..., None])
        if softcap:
            ds = ds * (1.0 - t * t)
        dq_blk = jnp.einsum("bhgqk,bkhd->bqhgd", ds, k_blk) * scale
        dk_blk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, q_blk) * scale
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, jax.lax.dynamic_slice_in_dim(dq, qs, qc, 1) + dq_blk, qs, 1)
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk, jax.lax.dynamic_slice_in_dim(dk, ks, kc, 1) + dk_blk, ks, 1)
        dv_ = jax.lax.dynamic_update_slice_in_dim(
            dv_, jax.lax.dynamic_slice_in_dim(dv_, ks, kc, 1) + dv_blk, ks, 1)
        return (dq, dk, dv_), None

    (dq, dk, dv_), _ = jax.lax.scan(body, (dq0, dk0, dv0), pairs)
    return (dq[:, :Tq].astype(q.dtype), dk[:, :Tk].astype(k.dtype),
            dv_[:, :Tk].astype(v.dtype))


_attend_core.defvjp(_attend_fwd, _attend_bwd)


# --------------------------------------------------------------- GQA block
def gqa_defs(cfg: ModelConfig):
    out_scale = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    d = {
        "wq": pd((cfg.d_model, cfg.n_heads, cfg.head_dim),
                 ("embed", "heads", "qk"), dtype=cfg.pdtype),
        "wk": pd((cfg.d_model, cfg.n_kv_heads, cfg.head_dim),
                 ("embed", "kv_heads", "qk"), dtype=cfg.pdtype),
        "wv": pd((cfg.d_model, cfg.n_kv_heads, cfg.head_dim),
                 ("embed", "kv_heads", "qk"), dtype=cfg.pdtype),
        "wo": pd((cfg.n_heads, cfg.head_dim, cfg.d_model),
                 ("heads", "qk", "embed"), scale=out_scale, dtype=cfg.pdtype),
    }
    return d


def gqa_project(cfg: ModelConfig, p, x, ctx: ShardCtx, positions):
    """x (B,S,D) → q (B,S,Hkv,G,dh), k,v (B,S,Hkv,dh). Applies rope."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = ctx.constrain(q, ("batch", None, "heads", None))
    k = ctx.constrain(k, ("batch", None, "kv_heads", None))
    v = ctx.constrain(v, ("batch", None, "kv_heads", None))
    if cfg.use_rope:
        cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    G = cfg.n_heads // cfg.n_kv_heads
    B, S = q.shape[:2]
    q = q.reshape(B, S, cfg.n_kv_heads, G, cfg.head_dim)
    return q, k, v


def gqa_attention(cfg: ModelConfig, p, x, ctx: ShardCtx, *, window: int,
                  positions, causal: bool = True):
    """Full training/prefill GQA attention block (no cache)."""
    q, k, v = gqa_project(cfg, p, x, ctx, positions)
    scale = cfg.head_dim ** -0.5
    out = attend_chunked(q, k, v, scale=scale, causal=causal, window=window,
                         softcap=cfg.attn_softcap, q_chunk=cfg.attn_chunk,
                         kv_chunk=cfg.attn_chunk)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads, cfg.head_dim)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return ctx.constrain(o, ("batch", "seq", None))


# --------------------------------------------------------------- MLA block
def mla_defs(cfg: ModelConfig):
    m = cfg.mla
    out_scale = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    return {
        "wdq": pd((cfg.d_model, m.q_lora), ("embed", "lora"), dtype=cfg.pdtype),
        "q_norm": rmsnorm_def(m.q_lora),
        "wuq": pd((m.q_lora, cfg.n_heads, m.nope_dim + m.rope_dim),
                  ("lora", "heads", "qk"), dtype=cfg.pdtype),
        "wdkv": pd((cfg.d_model, m.kv_lora), ("embed", "lora"), dtype=cfg.pdtype),
        "kv_norm": rmsnorm_def(m.kv_lora),
        "wukv": pd((m.kv_lora, cfg.n_heads, m.nope_dim + m.v_dim),
                   ("lora", "heads", "qk"), dtype=cfg.pdtype),
        "wkr": pd((cfg.d_model, m.rope_dim), ("embed", "qk"), dtype=cfg.pdtype),
        "wo": pd((cfg.n_heads, m.v_dim, cfg.d_model),
                 ("heads", "v", "embed"), scale=out_scale, dtype=cfg.pdtype),
    }


def mla_latents(cfg: ModelConfig, p, x, ctx: ShardCtx, positions):
    """Compressed latents: c_kv (B,S,kv_lora), k_rope (B,S,1,rope) — this pair
    *is* the MLA KV cache."""
    m = cfg.mla
    c_kv = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wdkv"]), p["kv_norm"],
                   cfg.norm_eps)
    k_r = jnp.einsum("bsd,dr->bsr", x, p["wkr"])[:, :, None, :]
    cos, sin = rope_tables(positions, m.rope_dim, cfg.rope_theta)
    k_r = apply_rope(k_r, cos, sin)
    return c_kv, k_r


def mla_queries(cfg: ModelConfig, p, x, ctx: ShardCtx, positions):
    m = cfg.mla
    cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wdq"]), p["q_norm"],
                 cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wuq"])
    q = ctx.constrain(q, ("batch", None, "heads", None))
    qn, qr = q[..., :m.nope_dim], q[..., m.nope_dim:]
    cos, sin = rope_tables(positions, m.rope_dim, cfg.rope_theta)
    qr = apply_rope(qr, cos, sin)
    return qn, qr


def mla_attention(cfg: ModelConfig, p, x, ctx: ShardCtx, *, window: int,
                  positions, causal: bool = True):
    """Training/prefill MLA: expand latents to full heads, run chunked core."""
    m = cfg.mla
    B, S, _ = x.shape
    qn, qr = mla_queries(cfg, p, x, ctx, positions)
    c_kv, k_r = mla_latents(cfg, p, x, ctx, positions)
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wukv"])
    kv = ctx.constrain(kv, ("batch", None, "heads", None))
    kn, v = kv[..., :m.nope_dim], kv[..., m.nope_dim:]
    k = jnp.concatenate([kn, jnp.broadcast_to(
        k_r, (B, S, cfg.n_heads, m.rope_dim)).astype(kn.dtype)], axis=-1)
    q = jnp.concatenate([qn, qr], axis=-1)[:, :, :, None, :]  # G=1
    scale = (m.nope_dim + m.rope_dim) ** -0.5
    out = attend_chunked(q, k, v, scale=scale, causal=causal, window=window,
                         softcap=cfg.attn_softcap, q_chunk=cfg.attn_chunk,
                         kv_chunk=cfg.attn_chunk)
    out = out.reshape(B, S, cfg.n_heads, m.v_dim)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return ctx.constrain(o, ("batch", "seq", None))


def attn_defs(cfg: ModelConfig):
    return mla_defs(cfg) if cfg.mla else gqa_defs(cfg)


# ------------------------------------------------- context-parallel (CP) GQA
def _gather_fsdp(x, spec, keep=("model",)):
    """All-gather every sharded dim except axes in `keep` (ZeRO-3)."""
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in reversed(axes):
            if ax not in keep:
                x = jax.lax.all_gather(x, ax, axis=dim, tiled=True)
    return x


def cp_gqa_attention(cfg: ModelConfig, p, x, ctx: ShardCtx, *, window: int,
                     causal: bool = True, return_kv: bool = False):
    """Context-parallel attention for archs whose head counts don't divide
    the ``model`` axis (gemma2 8H, GQA-8 archs, whisper 20H).

    q stays with its *local sequence rows*; only the (small, GQA) k/v are
    all-gathered over ``model``. Output rows are already seq-sharded, so the
    block has exactly ONE collective per projection set — no gathers inside
    the flash scan, no psum after the out-projection. Causal masking uses
    the traced per-shard q_offset (static block pruning is disabled; the
    rectangle waste shows up in §Roofline and is a §Perf lever)."""
    mesh = ctx.mesh
    xspec = ctx.spec(("batch", "seq", None), x.shape)
    pspecs = {n: ctx.spec(d.axes, d.shape) for n, d in gqa_defs(cfg).items()}
    G = cfg.n_heads // cfg.n_kv_heads

    def local(x_loc, params):
        i = jax.lax.axis_index("model")
        B, S_loc, D = x_loc.shape
        # CP parallelises the *sequence*: weights gather fully (ZeRO-3 over
        # data AND the head shards over model — heads don't divide msize)
        wq = _gather_fsdp(params["wq"], pspecs["wq"], keep=())
        wk = _gather_fsdp(params["wk"], pspecs["wk"], keep=())
        wv = _gather_fsdp(params["wv"], pspecs["wv"], keep=())
        wo = _gather_fsdp(params["wo"], pspecs["wo"], keep=())
        q = jnp.einsum("bsd,dhk->bshk", x_loc, wq)
        k = jnp.einsum("bsd,dhk->bshk", x_loc, wk)
        v = jnp.einsum("bsd,dhk->bshk", x_loc, wv)
        pos_loc = i * S_loc + jnp.arange(S_loc)
        if cfg.use_rope:
            cos, sin = rope_tables(pos_loc, cfg.head_dim, cfg.rope_theta)
            q = apply_rope(q, cos[None], sin[None])
            k = apply_rope(k, cos[None], sin[None])
        msize = ctx.axis_size("model")     # static mesh size (jax<0.5 has
        n_nb = -(-window // S_loc) if window else msize  # no lax.axis_size)
        if window and n_nb < msize - 1:
            # window-aware neighbor exchange: shard i only needs kv from
            # [i·S_loc − window, (i+1)·S_loc) → its own rows + n_nb left
            # neighbors via collective_permute — wire and attend-flops drop
            # msize/(n_nb+1)× vs a full all-gather (§Perf iteration 11)
            parts_k, parts_v = [k], [v]
            for d in range(1, n_nb + 1):
                perm = [(s, s + d) for s in range(msize - d)]
                parts_k.insert(0, jax.lax.ppermute(k, "model", perm))
                parts_v.insert(0, jax.lax.ppermute(v, "model", perm))
            kg = jnp.concatenate(parts_k, axis=1)
            vg = jnp.concatenate(parts_v, axis=1)
            kv_off = (i - n_nb) * S_loc
        else:
            kg = jax.lax.all_gather(k, "model", axis=1, tiled=True)
            vg = jax.lax.all_gather(v, "model", axis=1, tiled=True)
            kv_off = 0
        qg = q.reshape(B, S_loc, cfg.n_kv_heads, G, cfg.head_dim)
        out = _attend_scan(qg, kg, vg, scale=cfg.head_dim ** -0.5,
                           causal=causal, window=window,
                           softcap=cfg.attn_softcap,
                           q_chunk=min(cfg.attn_chunk, S_loc),
                           kv_chunk=cfg.attn_chunk,
                           q_offset=i * S_loc, kv_offset=kv_off)
        out = out.reshape(B, S_loc, cfg.n_heads, cfg.head_dim)
        o = jnp.einsum("bshk,hkd->bsd", out, wo)
        if return_kv:
            return o, k, v       # local rows → kv_seq-sharded cache, free
        return o

    bp = xspec[0]
    kvspec = P(bp, "model", None, None)
    out_specs = (xspec, kvspec, kvspec) if return_kv else xspec
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(xspec, {n: pspecs[n] for n in p}),
                       out_specs=out_specs, check_vma=False)
    return fn(x, dict(p))


def _cp_eligible(cfg: ModelConfig, ctx: ShardCtx) -> bool:
    msize = ctx.axis_size("model")
    if cfg.mla or msize == 1:
        return False
    return (cfg.n_kv_heads % msize != 0) or (cfg.n_heads % msize != 0)


def attention(cfg: ModelConfig, p, x, ctx: ShardCtx, *, window: int,
              positions, causal: bool = True):
    if cfg.mla:
        return mla_attention(cfg, p, x, ctx, window=window,
                             positions=positions, causal=causal)
    if _cp_eligible(cfg, ctx):
        return cp_gqa_attention(cfg, p, x, ctx, window=window, causal=causal)
    return gqa_attention(cfg, p, x, ctx, window=window, positions=positions,
                         causal=causal)


# ---------------------------------------------------------- cross-attention
def cross_attn_defs(cfg: ModelConfig):
    out_scale = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    return {
        "wq": pd((cfg.d_model, cfg.n_heads, cfg.head_dim),
                 ("embed", "heads", "qk"), dtype=cfg.pdtype),
        "wk": pd((cfg.d_model, cfg.n_kv_heads, cfg.head_dim),
                 ("embed", "kv_heads", "qk"), dtype=cfg.pdtype),
        "wv": pd((cfg.d_model, cfg.n_kv_heads, cfg.head_dim),
                 ("embed", "kv_heads", "qk"), dtype=cfg.pdtype),
        "wo": pd((cfg.n_heads, cfg.head_dim, cfg.d_model),
                 ("heads", "qk", "embed"), scale=out_scale, dtype=cfg.pdtype),
    }


def cross_kv(cfg: ModelConfig, p, enc_out, ctx: ShardCtx):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    k = ctx.constrain(k, ("batch", None, "kv_heads", None))
    v = ctx.constrain(v, ("batch", None, "kv_heads", None))
    return k, v


def cross_attention(cfg: ModelConfig, p, x, k, v, ctx: ShardCtx):
    """x: decoder states (B,Td,D); k/v: precomputed encoder KV (B,Te,H,dh)."""
    B, Td, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = ctx.constrain(q, ("batch", None, "heads", None))
    G = cfg.n_heads // cfg.n_kv_heads
    q = q.reshape(B, Td, cfg.n_kv_heads, G, cfg.head_dim)
    out = attend_chunked(q, k, v, scale=cfg.head_dim ** -0.5, causal=False,
                         q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk)
    out = out.reshape(B, Td, cfg.n_heads, cfg.head_dim)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return ctx.constrain(o, ("batch", "seq", None))


# ----------------------------------------------------------- pure reference
def reference_attention(q, k, v, *, scale, causal, window=0, softcap=0.0,
                        q_offset: int = 0):
    """O(T²)-memory oracle for tests. Same signature/layout as attend_chunked."""
    B, Tq, Hkv, G, dh = q.shape
    Tk = k.shape[1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(F32) * scale, k.astype(F32))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    iq = jnp.arange(Tq) + q_offset
    jk = jnp.arange(Tk)
    ok = jnp.ones((Tq, Tk), bool)
    if causal:
        ok &= jk[None, :] <= iq[:, None]
    if window:
        ok &= jk[None, :] > iq[:, None] - window
    s = jnp.where(ok[None, None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(F32))
    return out.astype(q.dtype)
