"""Pallas paged flash-decode attention — index the page table in-kernel.

The jnp paged path (the gathered-view oracle in ``ref.py``, formerly
`serve/decode.py::_paged_gather`) materializes a position-ordered
`(B, T·page_size, …)` copy of every slot's pages in HBM per layer, per
token, inside the quantum scan. This kernel never builds
that view: the grid is `(B, T)` with the page dimension innermost, the
page table and per-slot positions ride in as *scalar prefetch* operands
(`pltpu.PrefetchScalarGridSpec`), and each grid step DMAs exactly one
page's K/V block straight from the shared pool into VMEM — the BlockSpec
index map reads `pt[b, t]`, so the gather happens in the DMA engine, not
as an HBM-resident copy.

Attention is blockwise online softmax: `(acc, m, l)` carries live in VMEM
scratch across the page dimension, exactly as in
``kernels/flash_attention``. Table entries whose first position lies past
the slot's `pos` are skipped with ``pl.when`` (dead pages — including the
reserved trash page 0 that absorbs inactive-slot scribbles — cost no
FLOPs), and the tail page is position-masked. The kernel runs *per model
shard* inside the decode `shard_map`, so it returns **unnormalized**
`(o, m, l)` partials; the caller's exact-softmax `_combine` across the
``model`` axis is unchanged. The in-page write of the new token's K/V
stays a separate masked scatter outside the kernel (`_paged_write`): a
scatter through the table is one tiny row per slot — doing it in-kernel
would force the pool to be an aliased in/out operand for no bandwidth win.

Layouts (per shard; ``ps`` = page_size // msize, ``base`` = shard·ps):
  GQA: q (B, Hkv, G, dh); pools (N, ps, Hkv, dh) ×2 → o (B, Hkv·G, dh).
  MLA: q (B, H, R);       pool  (N, ps, R)          → o (B, H, kv_lora)
       (the cache row is both key and value — MQA-style absorbed MLA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _online_update(s, ok, acc_ref, m_ref, l_ref, ov):
    """One page block of flash accumulation. s (H, ps) masked scores, ok
    (H, ps) validity, ov(p) → (H, dv) value product for probabilities p."""
    m_old = m_ref[:, :1]                                   # (H, 1)
    m_blk = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_old, m_blk)
    m_safe = jnp.where(m_new <= NEG / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    p = jnp.where(ok, p, 0.0)
    corr = jnp.exp(jnp.where(m_old <= NEG / 2, NEG, m_old) - m_safe)
    acc_ref[...] = acc_ref[...] * corr + ov(p)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _store_partials(o_ref, m_ref_o, l_ref_o, acc_ref, m_ref, l_ref):
    """Emit the shard-local (o, m, l) partials for the cross-shard combine.
    o stays UNNORMALIZED — `_combine` rescales by exp(m - m_global) and
    divides by the psum'd l, so fully-masked shards contribute zero.
    m/l leave as (B, H, 1) arrays in (1, H, 1) blocks: the last two block
    dims then equal the array's, which Mosaic requires of a block whose
    dims are not multiples of (8, 128) — a (1, H) block of (B, H) is
    refused for B > 1. The wrappers drop the unit axis."""
    o_ref[0] = acc_ref[...]
    m_ref_o[0] = m_ref[...]
    l_ref_o[0] = l_ref[...]


def _gqa_kernel(pt_ref, pos_ref, base_ref, q_ref, k_ref, v_ref,
                o_ref, m_out, l_out, acc_ref, m_ref, l_ref, *,
                page_size: int, hkv: int, grp: int, nt: int, softcap: float,
                scale: float):
    b = pl.program_id(0)
    t = pl.program_id(1)
    ps = k_ref.shape[1]                                    # per-shard offsets
    H = hkv * grp

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    first = t * page_size + base_ref[0]                    # global pos of off 0

    @pl.when(first <= pos)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale           # (Hkv, G, dh)
        k = k_ref[0].astype(jnp.float32)                   # (ps, Hkv, dh)
        v = v_ref[0].astype(jnp.float32)                   # (ps, Hkv, dh)
        # per-kv-head 2D dots (static unroll — Hkv is a config constant)
        s = jnp.concatenate(
            [jax.lax.dot_general(q[h], k[:, h], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             for h in range(hkv)], axis=0)                 # (H, ps)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        gpos = first + jax.lax.broadcasted_iota(jnp.int32, (H, ps), 1)
        ok = gpos <= pos
        s = jnp.where(ok, s, NEG)

        def ov(p):                                         # (H, ps) → (H, dh)
            return jnp.concatenate(
                [jax.lax.dot_general(p[h * grp:(h + 1) * grp], v[:, h],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 for h in range(hkv)], axis=0)

        _online_update(s, ok, acc_ref, m_ref, l_ref, ov)

    @pl.when(t == nt - 1)
    def _store():
        _store_partials(o_ref, m_out, l_out, acc_ref, m_ref, l_ref)


def _mla_kernel(pt_ref, pos_ref, base_ref, q_ref, c_ref,
                o_ref, m_out, l_out, acc_ref, m_ref, l_ref, *,
                page_size: int, kv_lora: int, nt: int, scale: float):
    b = pl.program_id(0)
    t = pl.program_id(1)
    ps = c_ref.shape[1]
    H = q_ref.shape[1]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    first = t * page_size + base_ref[0]

    @pl.when(first <= pos)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale           # (H, R)
        c = c_ref[0].astype(jnp.float32)                   # (ps, R)
        s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        gpos = first + jax.lax.broadcasted_iota(jnp.int32, (H, ps), 1)
        ok = gpos <= pos
        s = jnp.where(ok, s, NEG)

        def ov(p):                                         # value = row[:lora]
            return jax.lax.dot_general(p, c[:, :kv_lora],
                                       (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

        _online_update(s, ok, acc_ref, m_ref, l_ref, ov)

    @pl.when(t == nt - 1)
    def _store():
        _store_partials(o_ref, m_out, l_out, acc_ref, m_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("page_size", "scale", "softcap",
                                             "interpret"))
def paged_flash_decode_gqa(q, pool_k, pool_v, page_table, pos, base, *,
                           page_size: int, scale: float, softcap: float = 0.0,
                           interpret: bool = False):
    """q (B,Hkv,G,dh); pools (N, ps, Hkv, dh); page_table (B, T) int32;
    pos (B,) int32; base () int32 shard offset (shard_idx · ps).
    → unnormalized partials o (B, Hkv·G, dh) f32, m/l (B, Hkv·G) f32."""
    B, hkv, grp, dh = q.shape
    ps = pool_k.shape[1]
    T = page_table.shape[1]
    H = hkv * grp
    grid = (B, T)
    scalars = (page_table.astype(jnp.int32), pos.astype(jnp.int32),
               jnp.asarray(base, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hkv, grp, dh), lambda b, t, pt, p, o: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, hkv, dh), lambda b, t, pt, p, o: (pt[b, t], 0, 0, 0)),
            pl.BlockSpec((1, ps, hkv, dh), lambda b, t, pt, p, o: (pt[b, t], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, dh), lambda b, t, pt, p, o: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, t, pt, p, o: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, t, pt, p, o: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, dh), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_gqa_kernel, page_size=page_size, hkv=hkv,
                             grp=grp, nt=T, softcap=softcap, scale=scale)
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        # the page axis carries the (acc, m, l) flash state → sequential;
        # batch rows are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, q, pool_k, pool_v)
    return o, m[..., 0], l[..., 0]


@functools.partial(jax.jit, static_argnames=("page_size", "kv_lora", "scale",
                                             "interpret"))
def paged_flash_decode_mla(q, pool, page_table, pos, base, *,
                           page_size: int, kv_lora: int, scale: float,
                           interpret: bool = False):
    """q (B,H,R); pool (N, ps, R); → o (B, H, kv_lora), m/l (B, H) f32
    partials. The pool row is both key (all R dims) and value (first
    kv_lora dims) — absorbed-MLA decode."""
    B, H, R = q.shape
    ps = pool.shape[1]
    T = page_table.shape[1]
    grid = (B, T)
    scalars = (page_table.astype(jnp.int32), pos.astype(jnp.int32),
               jnp.asarray(base, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, t, pt, p, o: (b, 0, 0)),
            pl.BlockSpec((1, ps, R), lambda b, t, pt, p, o: (pt[b, t], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, kv_lora), lambda b, t, pt, p, o: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, t, pt, p, o: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, t, pt, p, o: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, kv_lora), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_mla_kernel, page_size=page_size,
                             kv_lora=kv_lora, nt=T, scale=scale)
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, kv_lora), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, q, pool)
    return o, m[..., 0], l[..., 0]
