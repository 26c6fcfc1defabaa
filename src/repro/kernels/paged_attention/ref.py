"""Pure-jnp reference for the paged flash-decode kernel.

Computes the SAME shard-local unnormalized (o, m, l) partials as
``paged_attention.py`` by materializing the gathered view — this is the
equivalence oracle for the kernel tests, deliberately written in the
"generic" style the kernel replaces (one `jnp.take` over the page table,
direct global-max softmax). Numerics: both paths reduce in f32; the
online-softmax rescaling in the kernel is algebraically identical to the
single-max form here, so they agree to f32 round-off. A stacked pool
`(L, N, ps, …)` is read at `layer`, a one-layer pool `(N, ps, …)` as is.
"""
from __future__ import annotations

import jax.numpy as jnp

NEG = -1e30
F32 = jnp.float32


def _gathered(pool, page_table, base, page_size):
    """pool (N, ps, …) + pt (B, T) → (view (B, T·ps, …), gpos (B, T·ps))
    global positions per gathered offset for this shard (offset `base`)."""
    ps = pool.shape[1]
    B, T = page_table.shape
    g = jnp.take(pool, page_table, axis=0)                 # (B, T, ps, …)
    g = g.reshape((B, T * ps) + pool.shape[2:])
    gpos = (jnp.arange(T)[:, None] * page_size + base +
            jnp.arange(ps)[None]).reshape(-1)
    return g, jnp.broadcast_to(gpos[None], (B, T * ps))


def paged_flash_decode_gqa_ref(q, pool_k, pool_v, page_table, pos, base,
                               layer=0, *, page_size: int, scale: float,
                               softcap: float = 0.0):
    """Same contract as the kernel: q (B,Hkv,G,dh), pools (L,N,ps,Hkv,dh)
    or (N,ps,Hkv,dh) → (o (B,Hkv·G,dh), m (B,Hkv·G), l (B,Hkv·G)) f32
    partials."""
    B, hkv, grp, dh = q.shape
    if pool_k.ndim == 5:
        pool_k, pool_v = pool_k[layer], pool_v[layer]
    gk, gpos = _gathered(pool_k, page_table, base, page_size)
    gv, _ = _gathered(pool_v, page_table, base, page_size)
    valid = gpos <= pos[:, None]                           # (B, S)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(F32) * scale, gk.astype(F32))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(valid[:, None, None], s, NEG)
    m = jnp.max(s, -1)                                     # (B, Hkv, G)
    m_safe = jnp.where(m <= NEG / 2, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(valid[:, None, None], p, 0.0)
    o = jnp.einsum("bhgs,bshd->bhgd", p, gv.astype(F32))   # (B, Hkv, G, dh)
    l = jnp.sum(p, -1)
    H = hkv * grp
    return o.reshape(B, H, dh), m.reshape(B, H), l.reshape(B, H)


def paged_flash_decode_mla_ref(q, pool, page_table, pos, base, layer=0, *,
                               page_size: int, kv_lora: int, scale: float):
    """q (B,H,R); pool (L, N, ps, R) or (N, ps, R) → (o (B,H,kv_lora), m,
    l) f32 partials."""
    if pool.ndim == 4:
        pool = pool[layer]
    g, gpos = _gathered(pool, page_table, base, page_size)
    valid = gpos <= pos[:, None]
    s = jnp.einsum("bhr,bsr->bhs", q.astype(F32) * scale, g.astype(F32))
    s = jnp.where(valid[:, None], s, NEG)
    m = jnp.max(s, -1)
    m_safe = jnp.where(m <= NEG / 2, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(valid[:, None], p, 0.0)
    o = jnp.einsum("bhs,bsr->bhr", p, g[..., :kv_lora].astype(F32))
    return o, m, jnp.sum(p, -1)
