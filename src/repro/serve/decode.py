"""Single-token decode with flash-decoding (sequence-parallel KV attention).

The KV cache's sequence dim is sharded over ``model``; each shard computes
attention partials (o, m, l) over its slice and the exact softmax is
reconstructed with a max/psum tree — the TPU analogue of flash-decoding.
Cache writes are *local masked* updates inside the same shard_map (the
writing shard is the one whose slice contains `pos`) — no cross-shard
scatter appears in the HLO. Per-sequence positions (B,) support continuous
batching; sliding-window layers use ring addressing (pos mod window).

MLA decodes in the compressed latent space via the absorbed-weights trick:
the cache row *is* both key and value (MQA-style, dim kv_lora+rope).

Paged decode has two implementations selected by ``paged_kernel``:
  * the default Pallas kernel path (`kernels/paged_attention`) — the page
    table is scalar-prefetched and indexed *in-kernel*, one page block per
    grid step, online-softmax carries in VMEM; the gathered `(B, T·ps, …)`
    view never exists in HBM and the engine passes only the *live* prefix
    of the table (bucketed), so decode cost scales with context, not with
    the table width `max_len/page_size`;
  * ``paged_kernel=False`` — the jnp gathered-view implementation
    (`kernels/paged_attention/ref.py`, the PR 2 path) at full table
    width, kept as the escape hatch and the equivalence oracle.
In both, the in-page write of the new token's K/V is a masked B-row
scatter (`_stacked_write`) just before the attention reads the pool.

Page pools are carried, not scanned: `decode_step`'s layer scan holds each
pooled leaf as the whole `(L, N, ps, …)` stack in its carry and hands the
layer index down, so the write lands at `(layer, page, offset)` in place
and the kernel reads `(layer, pt[b, t])` straight from the stack. As a
scanned `xs`/`ys` leaf every layer's pool was sliced out of the stack and
written back each token, and the whole stack copied once per step — pool
traffic the size of the cache, for one new row per slot. Dense leaves
(rings, mamba states, non-paged caches) are per-slot and stay scanned.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models.layers import apply_rope, mlp, rmsnorm, rope_tables, _softcap
from repro.models.transformer import layer_schedule
from repro.serve.kv_cache import _is_pooled
from repro.sharding.axes import ShardCtx

F32 = jnp.float32
NEG = -1e30


# ------------------------------------------------------------ flash decode
def _combine(o, m, l):
    """Cross-shard exact-softmax combine of (o, m, l) partials."""
    m_g = jax.lax.pmax(m, "model")
    m_safe = jnp.where(m_g <= NEG / 2, 0.0, m_g)
    c = jnp.exp(jnp.where(m <= NEG / 2, NEG, m) - m_safe)
    o = jax.lax.psum(o * c[..., None], "model")
    l = jax.lax.psum(l * c, "model")
    return o / jnp.maximum(l, 1e-30)[..., None]


def _local_write(cache, new_row, rel):
    """cache (B, S_loc, …), new_row (B, …), rel (B,) local index (may be out
    of this shard's range → masked no-op)."""
    B, S_loc = cache.shape[0], cache.shape[1]
    in_range = (rel >= 0) & (rel < S_loc)
    relc = jnp.clip(rel, 0, S_loc - 1)
    b = jnp.arange(B)
    cur = cache[b, relc]                                   # (B, …)
    mask = in_range.reshape((B,) + (1,) * (cache.ndim - 2))
    upd = jnp.where(mask, new_row, cur)
    return cache.at[b, relc].set(upd)


def _paged_write(pool, new_row, pt, pos, i, msize):
    """Masked write of `new_row` (B,…) at logical position `pos` (B,) through
    page table `pt` (B,T) into `pool` (N, ps_loc, …). Shard `i` owns in-page
    offsets [i·ps_loc, (i+1)·ps_loc); out-of-range rows are a no-op. Distinct
    live slots hold disjoint pages (allocator invariant), so batch scatters
    never collide except on the reserved trash page 0."""
    B = new_row.shape[0]
    N, ps_loc = pool.shape[0], pool.shape[1]
    T = pt.shape[1]
    ps = ps_loc * msize
    idx = jnp.minimum(pos // ps, T - 1)
    page = jnp.take_along_axis(pt, idx[:, None], axis=1)[:, 0]
    # a slot frozen at pos == max_len (prompt_len = max_len-1 case) still
    # scribbles each step; route it to the trash page, never a live one
    page = jnp.where(pos < T * ps, page, 0)
    if msize == 1:          # every offset is in range on a 1-shard model axis
        return pool.at[page, pos % ps].set(new_row)
    rel = pos % ps - i * ps_loc
    in_range = (rel >= 0) & (rel < ps_loc)
    relc = jnp.clip(rel, 0, ps_loc - 1)
    pagec = jnp.clip(page, 0, N - 1)
    cur = pool[pagec, relc]                                # (B, …)
    mask = in_range.reshape((B,) + (1,) * (pool.ndim - 2))
    return pool.at[pagec, relc].set(jnp.where(mask, new_row, cur))


def _stacked_write(pool, new_row, pt, pos, i, msize, layer):
    """`_paged_write` into layer `layer` of a stacked pool (L, N, ps_loc, …):
    the stack is viewed as L·N pages (a free reshape) and the table offset
    by layer·N, so the write stays a scatter of B rows, which XLA performs
    in place on a loop carry. A frozen slot's scribble lands in page 0 of
    layer 0, which is trash in every layer."""
    L, N = pool.shape[:2]
    flat = pool.reshape((L * N,) + pool.shape[2:])
    return _paged_write(flat, new_row, pt + layer * N, pos, i,
                        msize).reshape(pool.shape)


def _paged_impl(paged_kernel) -> str:
    """Map the user-facing ``paged_kernel`` flag onto a
    `kernels/paged_attention/ops.py` impl name: True → backend auto
    (compiled kernel on TPU, jnp ref elsewhere), a string → forwarded
    verbatim, False → the jnp gathered-view oracle ("ref") — the PR 2
    escape hatch, now one shared implementation instead of an inline
    copy."""
    if isinstance(paged_kernel, (bool, int)):    # 0/1 behave as the bools
        return "" if paged_kernel else "ref"
    return paged_kernel


def _check_paged_args(page_table, pos, *, update: bool = True,
                      window: int = 0) -> None:
    """Typed validation shared by both paged decode entry points — these are
    user-reachable through Engine/flash_decode callers, so they raise
    ValueError instead of tripping asserts (PR 2 convention)."""
    if not update:
        raise ValueError(
            "paged decode always writes the new token's K/V; attend-only "
            "(update=False) callers must use the dense cache path")
    if window:
        raise ValueError(
            f"paged cache is full-attention only (window={window}); "
            "sliding-window layers keep their dense ring buffers")
    if page_table.ndim != 2:
        raise ValueError(
            f"page_table must be (batch, table_width) int32, got shape "
            f"{page_table.shape}")
    if page_table.shape[0] != pos.shape[0]:
        raise ValueError(
            f"page_table batch {page_table.shape[0]} != pos batch "
            f"{pos.shape[0]}")


def flash_decode_gqa(q, k_new, v_new, ck, cv, pos, *, window: int,
                     scale: float, softcap: float, ctx: ShardCtx,
                     update: bool = True, page_table=None,
                     paged_kernel=True, layer=None):
    """q (B,Hkv,G,dh); k_new/v_new (B,Hkv,dh); ck/cv (B,Sc,Hkv,dh) kv_seq-
    sharded; pos (B,). → (out (B,Hkv,G,dh), ck', cv').

    update=False → attend-only (whisper cross-attention; pos = valid_len-1).
    page_table (B,T) int32 → paged mode: ck/cv are shared page pools
    (num_pages, page_size, Hkv, dh) with the in-page offset kv_seq-sharded
    (full attention only — rings stay dense); with `layer` (a traced
    index) they are the stacked pools (L, num_pages, page_size, Hkv, dh)
    of the decode layer scan, written and read at that layer in place,
    and returned whole. ``paged_kernel`` selects the
    Pallas in-kernel table walk (True, or an impl string forwarded to
    `kernels/paged_attention/ops.py`) vs. the jnp gathered-view escape
    hatch (False). Either way the per-shard (o, m, l) partials meet the
    same exact-softmax `_combine` across the model axis.
    """
    mesh = ctx.mesh
    bp = ctx.spec(("batch", None, None, None), q.shape)[0]
    qspec = P(bp, None, None, None)
    nspec = P(bp, None, None)
    pspec = P(bp)

    msize = ctx.axis_size("model")         # static (jax<0.5: no lax.axis_size)

    if page_table is not None:
        _check_paged_args(page_table, pos, update=update, window=window)
        one = layer is None                # one layer's pool: a stack of one
        if one:
            ck, cv, layer = ck[None], cv[None], 0
        poolspec = ctx.spec(("layers", None, "kv_seq", "kv_heads", None),
                            ck.shape)
        ptspec = P(bp, None)
        # paged_kernel=False → pin the jnp gathered-view oracle (ref.py):
        # full-width table, PR 2 cost model, one shared implementation
        impl = _paged_impl(paged_kernel)

        def local_paged(q, kn, vn, pk, pv, pos, pt, ly):
            i = jax.lax.axis_index("model")
            pk = _stacked_write(pk, kn, pt, pos, i, msize, ly)
            pv = _stacked_write(pv, vn, pt, pos, i, msize, ly)
            B, hkv, grp, dh = q.shape
            o, m, l = paged_ops.paged_attend_gqa(
                q, pk, pv, pt, pos, i, msize, ly, scale=scale,
                softcap=softcap, impl=impl)
            o = o.reshape(B, hkv, grp, dh)
            m = m.reshape(B, hkv, grp)
            l = l.reshape(B, hkv, grp)
            return _combine(o, m, l).astype(q.dtype), pk, pv

        fn = jax.shard_map(local_paged, mesh=mesh,
                           in_specs=(qspec, nspec, nspec, poolspec, poolspec,
                                     pspec, ptspec, P()),
                           out_specs=(qspec, poolspec, poolspec),
                           check_vma=False)
        out, ck, cv = fn(q, k_new, v_new, ck, cv, pos, page_table,
                         jnp.asarray(layer, jnp.int32))
        return (out, ck[0], cv[0]) if one else (out, ck, cv)

    cspec = ctx.spec(("batch", "kv_seq", "kv_heads", None), ck.shape)

    def local(q, kn, vn, ck, cv, pos):
        i = jax.lax.axis_index("model")
        B, S_loc = ck.shape[0], ck.shape[1]
        S_tot = S_loc * msize
        if update:
            wpos = pos % S_tot if window else pos       # ring for windows
            rel = wpos - i * S_loc
            ck = _local_write(ck, kn, rel)
            cv = _local_write(cv, vn, rel)
        gpos = i * S_loc + jnp.arange(S_loc)            # (S_loc,) slot ids
        if window:
            # slot j holds absolute position p_j = pos - ((pos - j) mod S_tot)
            p_j = pos[:, None] - ((pos[:, None] - gpos[None]) % S_tot)
            valid = (p_j >= 0) & (p_j > pos[:, None] - window)
        else:
            valid = gpos[None] <= pos[:, None]          # (B, S_loc)
        s = jnp.einsum("bhgd,bshd->bhgs", q.astype(F32) * scale,
                       ck.astype(F32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid[:, None, None], s, NEG)
        m = jnp.max(s, -1)
        m_safe = jnp.where(m <= NEG / 2, 0.0, m)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[:, None, None], p, 0.0)
        o = jnp.einsum("bhgs,bshd->bhgd", p, cv.astype(F32))
        l = jnp.sum(p, -1)
        return _combine(o, m, l).astype(q.dtype), ck, cv

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(qspec, nspec, nspec, cspec, cspec, pspec),
                       out_specs=(qspec, cspec, cspec), check_vma=False)
    return fn(q, k_new, v_new, ck, cv, pos)


def flash_decode_mla(q_eff, new_row, ckv, pos, *, kv_lora: int, scale: float,
                     ctx: ShardCtx, page_table=None, paged_kernel=True,
                     layer=None):
    """q_eff (B,H,R); new_row (B,R); ckv (B,Sc,R). Key = cache row, value =
    first kv_lora dims of the same row. page_table → ckv is the shared pool
    (num_pages, page_size, R), or with `layer` the stacked
    (L, num_pages, page_size, R); `paged_kernel` and `layer` as in
    flash_decode_gqa (MLA shares the full-attention-only constraint —
    typed check, not assert)."""
    mesh = ctx.mesh
    bp = ctx.spec(("batch", None, None), q_eff.shape)[0]
    qspec = P(bp, None, None)
    nspec = P(bp, None)
    pspec = P(bp)
    msize = ctx.axis_size("model")

    if page_table is not None:
        _check_paged_args(page_table, pos)
        one = layer is None                # one layer's pool: a stack of one
        if one:
            ckv, layer = ckv[None], 0
        poolspec = ctx.spec(("layers", None, "kv_seq", None), ckv.shape)
        ptspec = P(bp, None)
        impl = _paged_impl(paged_kernel)

        def local_paged(q, row, pool, pos, pt, ly):
            i = jax.lax.axis_index("model")
            pool = _stacked_write(pool, row, pt, pos, i, msize, ly)
            o, m, l = paged_ops.paged_attend_mla(
                q, pool, pt, pos, i, msize, ly, kv_lora=kv_lora,
                scale=scale, impl=impl)
            return _combine(o, m, l).astype(q.dtype), pool

        fn = jax.shard_map(local_paged, mesh=mesh,
                           in_specs=(qspec, nspec, poolspec, pspec, ptspec,
                                     P()),
                           out_specs=(qspec, poolspec), check_vma=False)
        out, ckv = fn(q_eff, new_row, ckv, pos, page_table,
                      jnp.asarray(layer, jnp.int32))
        return (out, ckv[0]) if one else (out, ckv)

    cspec = ctx.spec(("batch", "kv_seq", None), ckv.shape)

    def local(q, row, ckv, pos):
        i = jax.lax.axis_index("model")
        B, S_loc, R = ckv.shape
        rel = pos - i * S_loc
        ckv = _write3(ckv, row, rel)
        gpos = i * S_loc + jnp.arange(S_loc)
        valid = gpos[None] <= pos[:, None]
        s = jnp.einsum("bhr,bsr->bhs", q.astype(F32) * scale,
                       ckv.astype(F32))
        s = jnp.where(valid[:, None], s, NEG)
        m = jnp.max(s, -1)
        m_safe = jnp.where(m <= NEG / 2, 0.0, m)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[:, None], p, 0.0)
        o = jnp.einsum("bhs,bsr->bhr", p, ckv[..., :kv_lora].astype(F32))
        l = jnp.sum(p, -1)
        return _combine(o, m, l).astype(q.dtype), ckv

    _write3 = _local_write

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(qspec, nspec, cspec, pspec),
                       out_specs=(qspec, cspec), check_vma=False)
    return fn(q_eff, new_row, ckv, pos)


# --------------------------------------------------------- per-block decode
def gqa_decode(cfg: ModelConfig, p, x, cache, pos, window, ctx: ShardCtx,
               page_table=None, paged_kernel=True, layer=None):
    """x (B,D) → (out (B,D), new cache); `layer` as in flash_decode_gqa."""
    B = x.shape[0]
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    if cfg.use_rope:
        cos, sin = rope_tables(pos, cfg.head_dim, cfg.rope_theta)  # (B, dh/2)
        q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
        k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
    out, ck, cv = flash_decode_gqa(
        qg, k, v, cache["k"], cache["v"], pos, window=window,
        scale=cfg.head_dim ** -0.5, softcap=cfg.attn_softcap, ctx=ctx,
        page_table=page_table, paged_kernel=paged_kernel, layer=layer)
    out = out.reshape(B, cfg.n_heads * cfg.head_dim)
    o = jnp.einsum("bk,kd->bd",
                   out, p["wo"].reshape(-1, cfg.d_model))
    return ctx.constrain(o, ("batch", None)), {"k": ck, "v": cv}


def mla_decode(cfg: ModelConfig, p, x, cache, pos, ctx: ShardCtx,
               page_table=None, paged_kernel=True, layer=None):
    m = cfg.mla
    B = x.shape[0]
    x3 = x[:, None, :]
    # queries
    cq = rmsnorm(jnp.einsum("bd,dr->br", x, p["wdq"]), p["q_norm"],
                 cfg.norm_eps)
    q = jnp.einsum("br,rhk->bhk", cq, p["wuq"])
    qn, qr = q[..., :m.nope_dim], q[..., m.nope_dim:]
    cos, sin = rope_tables(pos, m.rope_dim, cfg.rope_theta)
    qr = apply_rope(qr[:, None], cos[:, None], sin[:, None])[:, 0]
    # absorbed query: q_c = qn · W_uk  → latent space
    wuk = p["wukv"][..., :m.nope_dim]                  # (R, H, nope)
    q_c = jnp.einsum("bhn,rhn->bhr", qn, wuk)          # (B, H, kv_lora)
    q_eff = jnp.concatenate([q_c, qr], axis=-1)
    # new cache row
    ckv_t = rmsnorm(jnp.einsum("bd,dr->br", x, p["wdkv"]), p["kv_norm"],
                    cfg.norm_eps)
    kr_t = jnp.einsum("bd,dr->br", x, p["wkr"])
    kr_t = apply_rope(kr_t[:, None, None], cos[:, None], sin[:, None])[:, 0, 0]
    row = jnp.concatenate([ckv_t, kr_t], axis=-1).astype(cache["ckv"].dtype)
    scale = (m.nope_dim + m.rope_dim) ** -0.5
    o_c, ckv = flash_decode_mla(q_eff, row, cache["ckv"], pos,
                                kv_lora=m.kv_lora, scale=scale, ctx=ctx,
                                page_table=page_table,
                                paged_kernel=paged_kernel, layer=layer)
    # un-absorb values: o = (o_c · W_uv) then output proj
    wuv = p["wukv"][..., m.nope_dim:]                  # (R, H, v)
    o = jnp.einsum("bhr,rhv->bhv", o_c, wuv)
    o = jnp.einsum("bhv,hvd->bd", o, p["wo"])
    return ctx.constrain(o, ("batch", None)), {"ckv": ckv}


def block_decode(cfg: ModelConfig, bc, p, cache, h, pos, ctx: ShardCtx,
                 page_table=None, paged_kernel=True, layer=None):
    """`layer` set → `cache` holds this slot's stacked page pools, read and
    written at that index (see `decode_step`)."""
    x = rmsnorm(h, p["norm1"], cfg.norm_eps)
    if bc.mixer == "attn":
        # only full-attention layers are paged; rings keep dense buffers
        pt = None if bc.window else page_table
        if cfg.mla:
            y, new_cache = mla_decode(cfg, p["attn"], x, cache, pos, ctx,
                                      page_table=pt,
                                      paged_kernel=paged_kernel, layer=layer)
        else:
            y, new_cache = gqa_decode(cfg, p["attn"], x, cache, pos,
                                      bc.window, ctx, page_table=pt,
                                      paged_kernel=paged_kernel, layer=layer)
    else:
        step = (mamba_mod.mamba2_step if cfg.ssm.version == 2
                else mamba_mod.mamba1_step)
        y, new_cache = step(cfg, p["mamba"], x, cache, ctx)
    if cfg.use_post_norm:
        y = rmsnorm(y, p["post1"], cfg.norm_eps)
    h = h + y
    if bc.ffn != "none":
        x = rmsnorm(h, p["norm2"], cfg.norm_eps)
        if bc.ffn == "moe":
            y = moe_mod.moe_decode(cfg, p["moe"], x, ctx)
        else:
            y = mlp(cfg, p["mlp"], x[:, None], ctx)[:, 0]
        if cfg.use_post_norm:
            y = rmsnorm(y, p["post2"], cfg.norm_eps)
        h = h + y
    return h, new_cache


# ------------------------------------------------------------- decode step
def decode_step(cfg: ModelConfig, params, cache, tokens, pos, ctx: ShardCtx,
                page_table=None, paged_kernel=True):
    """tokens (B,), pos (B,) → (logits (B,V) f32 vocab-sharded, new cache).
    page_table (B,T) → full-attention cache leaves are page pools.

    Pooled slots ride in the layer scan's carry as whole `(L, N, ps, …)`
    stacks, addressed by the scanned layer index; every other slot is
    scanned per layer as `xs`/`ys` (module docstring)."""
    segments = layer_schedule(cfg)
    h = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.pdtype)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    h = ctx.constrain(h, ("batch", None))
    new_blocks = []
    for seg, sp, sc in zip(segments, params["blocks"], cache["blocks"]):
        pooled = {f"s{j}" for j, bc in enumerate(seg.pattern)
                  if page_table is not None and _is_pooled(bc)}
        pools = {k: v for k, v in sc.items() if k in pooled}
        rest = {k: v for k, v in sc.items() if k not in pooled}

        def body(carry, xs, seg=seg, pooled=pooled):
            hc, pools = carry
            layer, slot_params, slot_cache = xs
            pools, new_slot = dict(pools), {}
            for j, bc in enumerate(seg.pattern):
                key = f"s{j}"
                if key in pooled:
                    hc, pools[key] = block_decode(
                        cfg, bc, slot_params[key], pools[key], hc, pos, ctx,
                        page_table=page_table, paged_kernel=paged_kernel,
                        layer=layer)
                else:
                    hc, new_slot[key] = block_decode(
                        cfg, bc, slot_params[key], slot_cache[key], hc, pos,
                        ctx, page_table=page_table,
                        paged_kernel=paged_kernel)
            return (hc, pools), new_slot

        (h, pools), new_sc = jax.lax.scan(
            body, (h, pools), (jnp.arange(seg.repeat), sp, rest))
        new_blocks.append({**new_sc, **pools})
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    w = (params["embed"]["table"].T if cfg.tie_embeddings
         else params["unembed"]["w"])
    logits = jnp.einsum("bd,dv->bv", h, w.astype(h.dtype),
                        preferred_element_type=F32)
    logits = _softcap(logits, cfg.final_softcap)
    logits = ctx.constrain(logits, ("batch", "vocab"))
    return logits, {"blocks": new_blocks}


# ------------------------------------------------------ fused decode loop
def _filter_logits(logits, *, temperature: float, top_k: int,
                   top_p: float = 0.0):
    """Temperature / top-k / nucleus (top-p) filtering → f32 logits ready
    for `jax.random.categorical` (truncated entries at NEG). All three
    knobs are *static* Python floats/ints: `temperature` must be > 0 here
    (greedy never builds a distribution), and top_p in {0, 1.0} — nucleus
    off — adds no HLO at all, so a top_p=1.0 sampler traces to the exact
    same jaxpr as the pre-nucleus sampler."""
    lg = logits.astype(F32) / temperature
    if top_k:
        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]        # (…, 1)
        lg = jnp.where(lg < kth, NEG, lg)
    if top_p and top_p < 1.0:
        probs = jax.nn.softmax(lg, axis=-1)
        srt = jnp.sort(probs, axis=-1)[..., ::-1]          # descending
        csum = jnp.cumsum(srt, axis=-1)
        # smallest prefix whose mass reaches top_p; (csum - srt) is the mass
        # *before* each entry, so the count is always ≥ 1 (never empty)
        n_keep = jnp.sum((csum - srt < top_p).astype(jnp.int32),
                         axis=-1, keepdims=True)
        thr = jnp.take_along_axis(srt, n_keep - 1, axis=-1)
        lg = jnp.where(probs < thr, NEG, lg)
    return lg


def _sample_tokens(logits, key, *, temperature: float, top_k: int,
                   top_p: float = 0.0):
    """Next-token choice on device. `temperature` is a *static* float:
    0 → greedy argmax (no PRNG consumed, HLO identical to the PR 1 loop);
    > 0 → temperature-scaled (optionally top-k / top-p truncated)
    categorical."""
    if not temperature:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    lg = _filter_logits(logits, temperature=temperature, top_k=top_k,
                        top_p=top_p)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


def decode_loop(cfg: ModelConfig, params, cache, tokens, pos, active,
                remaining, ctx: ShardCtx, *, num_steps: int, eos_id: int,
                max_len: int, page_table=None, paged_kernel=True,
                temperature: float = 0.0, top_k: int = 0,
                top_p: float = 0.0, rng=None):
    """Multi-token decode fused into one device program.

    Wraps `decode_step` in a `jax.lax.scan` over a quantum of `num_steps`
    tokens with sampling *on device* and per-slot done masking, so the host
    syncs once per quantum instead of once per token (DESIGN.md §"Serving
    fast path"). Sampling is greedy argmax at `temperature=0` and
    temperature/top-k categorical otherwise; the PRNG key rides in the scan
    carry (split once per step), so real sampling costs zero extra host
    syncs. All carries are (B,)-or-key device arrays the engine keeps
    resident between cycles; the engine jits this with the cache and state
    donated so decoding stops allocating a fresh cache every token.

    Masking: a slot emits while `active`; it deactivates when its token
    budget (`remaining`) drains, it samples `eos_id`, or its write position
    reaches `max_len - 1`. Inactive slots still run (batched decode is a
    fixed quantum) but their emissions are masked and their state frozen;
    whatever they scribble into their cache rows is overwritten by the next
    prefill insert into that slot.

    Returns ((cache, tokens, pos, active, remaining, rng),
             emitted (num_steps, B) int32, emitted_mask (num_steps, B) bool).
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def body(carry, _):
        cache, tokens, pos, active, remaining, key = carry
        logits, cache = decode_step(cfg, params, cache, tokens, pos, ctx,
                                    page_table=page_table,
                                    paged_kernel=paged_kernel)
        if temperature:
            key, sub = jax.random.split(key)
        else:
            sub = key
        nxt = _sample_tokens(logits, sub, temperature=temperature,
                             top_k=top_k, top_p=top_p)
        emit_tok = jnp.where(active, nxt, -1)
        remaining = remaining - active.astype(remaining.dtype)
        pos = pos + active.astype(pos.dtype)
        still = active & (remaining > 0) & (nxt != eos_id) & \
            (pos < max_len - 1)
        tokens = jnp.where(still, nxt, tokens)
        return (cache, tokens, pos, still, remaining, key), (emit_tok, active)

    carry = (cache, tokens, pos, active, remaining, rng)
    carry, (toks, msks) = jax.lax.scan(body, carry, None, length=num_steps)
    return carry, toks, msks


# ------------------------------------------------- speculative decode (§7)
def _merge_partials(o1, m1, l1, o2, m2, l2):
    """Online-softmax merge of two shard-local (o, m, l) partial triples.
    Both inputs are *unnormalized* (o = Σ e^{s-m}·v, l = Σ e^{s-m});
    `_combine` still runs once across the model axis afterwards."""
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(m <= NEG / 2, 0.0, m)
    c1 = jnp.exp(jnp.where(m1 <= NEG / 2, NEG, m1) - m_safe)
    c2 = jnp.exp(jnp.where(m2 <= NEG / 2, NEG, m2) - m_safe)
    o = o1 * c1[..., None] + o2 * c2[..., None]
    l = l1 * c1 + l2 * c2
    return o, m, l


def flash_verify_gqa(q, k_new, v_new, ck, cv, pos0, *, window: int,
                     scale: float, softcap: float, ctx: ShardCtx,
                     page_table=None, paged_kernel=True):
    """Batched K-token verify attention for speculative decode.

    q (B,K,Hkv,G,dh); k_new/v_new (B,K,Hkv,dh) the *staged* K/V rows for
    positions pos0..pos0+K-1; ck/cv the cache exactly as the last commit
    left it; pos0 (B,) the write position of verify input 0. → out
    (B,K,Hkv,G,dh). The cache is READ-ONLY here — query j (absolute
    position pos0+j) attends committed history (< pos0) plus staged rows
    j' ≤ j (self included), which reproduces the serial loop's
    write-then-attend semantics without mutating rows a rejected proposal
    would corrupt; `commit_rows` writes the accepted prefix afterwards.
    Staged scores are contributed by shard 0 only (every shard holds the
    full staged rows — adding them everywhere would double-count in the
    psum). Sliding-window layers require K ≤ window so every staged row
    stays inside every query's window; ring slots are anchored at the last
    committed position pos0-1."""
    mesh = ctx.mesh
    K = q.shape[1]
    if window and K > window:
        raise ValueError(f"verify block K={K} exceeds window={window}")
    bp = ctx.spec(("batch", None, None, None, None), q.shape)[0]
    qspec = P(bp, None, None, None, None)
    nspec = P(bp, None, None, None)
    pspec = P(bp)
    msize = ctx.axis_size("model")
    causal = jnp.arange(K)[:, None] >= jnp.arange(K)[None, :]   # (Kq, Kk)

    def _staged_partials(qf, kn, vn, i):
        # qf f32·scale (B,K,Hkv,G,dh); kn/vn (B,K,Hkv,dh)
        s = jnp.einsum("bkhgd,bjhd->bhgkj", qf, kn.astype(F32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        keep = jnp.logical_and(i == 0, causal)[None, None, None]
        s = jnp.where(keep, s, NEG)
        m = jnp.max(s, -1)
        m_safe = jnp.where(m <= NEG / 2, 0.0, m)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(keep, p, 0.0)
        o = jnp.einsum("bhgkj,bjhd->bhgkd", p, vn.astype(F32))
        return o, m, jnp.sum(p, -1)

    if page_table is not None:
        _check_paged_args(page_table, pos0, window=window)
        poolspec = ctx.spec((None, "kv_seq", "kv_heads", None), ck.shape)
        ptspec = P(bp, None)
        impl = _paged_impl(paged_kernel)

        def local_paged(q, kn, vn, pk, pv, pos0, pt):
            i = jax.lax.axis_index("model")
            B, K, hkv, grp, dh = q.shape
            qf = q.reshape(B * K, hkv, grp, dh)
            # committed history only: kernel validity is gpos ≤ pos, so
            # pass pos0-1 for every query (there is always a prefilled
            # prompt, so pos0 ≥ 1 whenever the slot's output is consumed)
            posf = jnp.repeat(pos0 - 1, K, axis=0)
            ptf = jnp.repeat(pt, K, axis=0)
            o, m, l = paged_ops.paged_attend_gqa(
                qf, pk, pv, ptf, posf, i, msize, scale=scale,
                softcap=softcap, impl=impl)
            o = jnp.moveaxis(o.reshape(B, K, hkv, grp, dh), 1, 3)
            m = jnp.moveaxis(m.reshape(B, K, hkv, grp), 1, 3)
            l = jnp.moveaxis(l.reshape(B, K, hkv, grp), 1, 3)
            o2, m2, l2 = _staged_partials(q.astype(F32) * scale, kn, vn, i)
            out = _combine(*_merge_partials(o, m, l, o2, m2, l2))
            return jnp.moveaxis(out, 3, 1).astype(q.dtype)

        fn = jax.shard_map(local_paged, mesh=mesh,
                           in_specs=(qspec, nspec, nspec, poolspec, poolspec,
                                     pspec, ptspec),
                           out_specs=qspec, check_vma=False)
        return fn(q, k_new, v_new, ck, cv, pos0, page_table)

    cspec = ctx.spec(("batch", "kv_seq", "kv_heads", None), ck.shape)

    def local(q, kn, vn, ck, cv, pos0):
        i = jax.lax.axis_index("model")
        B, S_loc = ck.shape[0], ck.shape[1]
        S_tot = S_loc * msize
        gpos = i * S_loc + jnp.arange(S_loc)
        qpos = pos0[:, None] + jnp.arange(K)[None]              # (B, K)
        if window:
            # ring content is anchored at the last *committed* position:
            # slot j holds p_j = (pos0-1) - ((pos0-1 - j) mod S_tot); the
            # staged rows cover pos0..pos0+K-1 and K ≤ window keeps them
            # all in-window for every query
            anchor = pos0[:, None] - 1
            p_j = anchor - ((anchor - gpos[None]) % S_tot)       # (B, S_loc)
            valid = (p_j >= 0)[:, None, :] & \
                (p_j[:, None, :] > qpos[:, :, None] - window)    # (B,K,S_loc)
        else:
            valid = jnp.broadcast_to(
                (gpos[None] < pos0[:, None])[:, None, :], (B, K, S_loc))
        qf = q.astype(F32) * scale
        s = jnp.einsum("bkhgd,bshd->bhgks", qf, ck.astype(F32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid[:, None, None], s, NEG)
        m = jnp.max(s, -1)
        m_safe = jnp.where(m <= NEG / 2, 0.0, m)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[:, None, None], p, 0.0)
        o = jnp.einsum("bhgks,bshd->bhgkd", p, cv.astype(F32))
        l = jnp.sum(p, -1)
        o2, m2, l2 = _staged_partials(qf, kn, vn, i)
        out = _combine(*_merge_partials(o, m, l, o2, m2, l2))
        return jnp.moveaxis(out, 3, 1).astype(q.dtype)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(qspec, nspec, nspec, cspec, cspec, pspec),
                       out_specs=qspec, check_vma=False)
    return fn(q, k_new, v_new, ck, cv, pos0)


def flash_verify_mla(q_eff, new_rows, ckv, pos0, *, kv_lora: int,
                     scale: float, ctx: ShardCtx, page_table=None,
                     paged_kernel=True):
    """MLA analogue of `flash_verify_gqa`: q_eff (B,K,H,R); new_rows
    (B,K,R) the staged latent rows; ckv (B,Sc,R) or the (N,ps,R) pool. →
    out (B,K,H,kv_lora). Read-only; full-attention only (typed check)."""
    mesh = ctx.mesh
    K = q_eff.shape[1]
    bp = ctx.spec(("batch", None, None, None), q_eff.shape)[0]
    qspec = P(bp, None, None, None)
    nspec = P(bp, None, None)
    pspec = P(bp)
    msize = ctx.axis_size("model")
    causal = jnp.arange(K)[:, None] >= jnp.arange(K)[None, :]

    def _staged_partials(qf, rows, i):
        # qf f32·scale (B,K,H,R); rows (B,K,R)
        s = jnp.einsum("bkhr,bjr->bhkj", qf, rows.astype(F32))
        keep = jnp.logical_and(i == 0, causal)[None, None]
        s = jnp.where(keep, s, NEG)
        m = jnp.max(s, -1)
        m_safe = jnp.where(m <= NEG / 2, 0.0, m)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(keep, p, 0.0)
        o = jnp.einsum("bhkj,bjr->bhkr", p, rows[..., :kv_lora].astype(F32))
        return o, m, jnp.sum(p, -1)

    if page_table is not None:
        _check_paged_args(page_table, pos0)
        poolspec = ctx.spec((None, "kv_seq", None), ckv.shape)
        ptspec = P(bp, None)
        impl = _paged_impl(paged_kernel)

        def local_paged(q, rows, pool, pos0, pt):
            i = jax.lax.axis_index("model")
            B, K, H, R = q.shape
            qf = q.reshape(B * K, H, R)
            posf = jnp.repeat(pos0 - 1, K, axis=0)
            ptf = jnp.repeat(pt, K, axis=0)
            o, m, l = paged_ops.paged_attend_mla(
                qf, pool, ptf, posf, i, msize, kv_lora=kv_lora,
                scale=scale, impl=impl)
            o = jnp.moveaxis(o.reshape(B, K, H, kv_lora), 1, 2)
            m = jnp.moveaxis(m.reshape(B, K, H), 1, 2)
            l = jnp.moveaxis(l.reshape(B, K, H), 1, 2)
            o2, m2, l2 = _staged_partials(q.astype(F32) * scale, rows, i)
            out = _combine(*_merge_partials(o, m, l, o2, m2, l2))
            return jnp.moveaxis(out, 2, 1).astype(q.dtype)

        fn = jax.shard_map(local_paged, mesh=mesh,
                           in_specs=(qspec, nspec, poolspec, pspec, ptspec),
                           out_specs=qspec, check_vma=False)
        return fn(q_eff, new_rows, ckv, pos0, page_table)

    cspec = ctx.spec(("batch", "kv_seq", None), ckv.shape)

    def local(q, rows, ckv, pos0):
        i = jax.lax.axis_index("model")
        S_loc = ckv.shape[1]
        gpos = i * S_loc + jnp.arange(S_loc)
        valid = gpos[None] < pos0[:, None]                      # (B, S_loc)
        qf = q.astype(F32) * scale
        s = jnp.einsum("bkhr,bsr->bhks", qf, ckv.astype(F32))
        s = jnp.where(valid[:, None, None], s, NEG)
        m = jnp.max(s, -1)
        m_safe = jnp.where(m <= NEG / 2, 0.0, m)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[:, None, None], p, 0.0)
        o = jnp.einsum("bhks,bsr->bhkr", p, ckv[..., :kv_lora].astype(F32))
        l = jnp.sum(p, -1)
        o2, m2, l2 = _staged_partials(qf, rows, i)
        out = _combine(*_merge_partials(o, m, l, o2, m2, l2))
        return jnp.moveaxis(out, 2, 1).astype(q.dtype)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(qspec, nspec, cspec, pspec),
                       out_specs=qspec, check_vma=False)
    return fn(q_eff, new_rows, ckv, pos0)


def gqa_verify(cfg: ModelConfig, p, x, cache, pos0, window, ctx: ShardCtx,
               page_table=None, paged_kernel=True):
    """x (B,K,D) → (out (B,K,D), staged {"k","v"} rows (B,K,Hkv,dh))."""
    B, K = x.shape[:2]
    q = jnp.einsum("bkd,dhe->bkhe", x, p["wq"])
    k = jnp.einsum("bkd,dhe->bkhe", x, p["wk"])
    v = jnp.einsum("bkd,dhe->bkhe", x, p["wv"])
    if cfg.use_rope:
        qpos = pos0[:, None] + jnp.arange(K)[None]
        cos, sin = rope_tables(qpos, cfg.head_dim, cfg.rope_theta)  # (B,K,·)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, K, cfg.n_kv_heads, G, cfg.head_dim)
    out = flash_verify_gqa(qg, k, v, cache["k"], cache["v"], pos0,
                           window=window, scale=cfg.head_dim ** -0.5,
                           softcap=cfg.attn_softcap, ctx=ctx,
                           page_table=page_table, paged_kernel=paged_kernel)
    out = out.reshape(B, K, cfg.n_heads * cfg.head_dim)
    o = jnp.einsum("bke,ed->bkd", out, p["wo"].reshape(-1, cfg.d_model))
    staged = {"k": k.astype(cache["k"].dtype),
              "v": v.astype(cache["v"].dtype)}
    return ctx.constrain(o, ("batch", None, None)), staged


def mla_verify(cfg: ModelConfig, p, x, cache, pos0, ctx: ShardCtx,
               page_table=None, paged_kernel=True):
    """x (B,K,D) → (out (B,K,D), staged {"ckv"} latent rows (B,K,R))."""
    m = cfg.mla
    cq = rmsnorm(jnp.einsum("bkd,dr->bkr", x, p["wdq"]), p["q_norm"],
                 cfg.norm_eps)
    q = jnp.einsum("bkr,rhe->bkhe", cq, p["wuq"])
    qn, qr = q[..., :m.nope_dim], q[..., m.nope_dim:]
    qpos = pos0[:, None] + jnp.arange(x.shape[1])[None]
    cos, sin = rope_tables(qpos, m.rope_dim, cfg.rope_theta)   # (B,K,·)
    qr = apply_rope(qr, cos, sin)
    wuk = p["wukv"][..., :m.nope_dim]                  # (R, H, nope)
    q_c = jnp.einsum("bkhn,rhn->bkhr", qn, wuk)
    q_eff = jnp.concatenate([q_c, qr], axis=-1)
    ckv_t = rmsnorm(jnp.einsum("bkd,dr->bkr", x, p["wdkv"]), p["kv_norm"],
                    cfg.norm_eps)
    kr_t = jnp.einsum("bkd,dr->bkr", x, p["wkr"])
    kr_t = apply_rope(kr_t[:, :, None], cos, sin)[:, :, 0]
    rows = jnp.concatenate([ckv_t, kr_t], axis=-1).astype(cache["ckv"].dtype)
    scale = (m.nope_dim + m.rope_dim) ** -0.5
    o_c = flash_verify_mla(q_eff, rows, cache["ckv"], pos0,
                           kv_lora=m.kv_lora, scale=scale, ctx=ctx,
                           page_table=page_table, paged_kernel=paged_kernel)
    wuv = p["wukv"][..., m.nope_dim:]                  # (R, H, v)
    o = jnp.einsum("bkhr,rhv->bkhv", o_c, wuv)
    o = jnp.einsum("bkhv,hvd->bkd", o, p["wo"])
    return ctx.constrain(o, ("batch", None, None)), {"ckv": rows}


def block_verify(cfg: ModelConfig, bc, p, cache, h, pos0, ctx: ShardCtx,
                 page_table=None, paged_kernel=True):
    """h (B,K,D) → (h', staged). Attention layers stage their K new
    K/V rows; mamba layers scan the single-token step over the K inputs
    and stage the K intermediate states (SSMs are inherently serial —
    verify only batches the attention/FFN work)."""
    x = rmsnorm(h, p["norm1"], cfg.norm_eps)
    if bc.mixer == "attn":
        pt = None if bc.window else page_table
        if cfg.mla:
            y, staged = mla_verify(cfg, p["attn"], x, cache, pos0, ctx,
                                   page_table=pt, paged_kernel=paged_kernel)
        else:
            y, staged = gqa_verify(cfg, p["attn"], x, cache, pos0,
                                   bc.window, ctx, page_table=pt,
                                   paged_kernel=paged_kernel)
    else:
        step = (mamba_mod.mamba2_step if cfg.ssm.version == 2
                else mamba_mod.mamba1_step)

        def sbody(state, xt):
            yt, nstate = step(cfg, p["mamba"], xt, state, ctx)
            return nstate, (yt, nstate)

        _, (ys, states) = jax.lax.scan(sbody, cache, jnp.moveaxis(x, 1, 0))
        y = jnp.moveaxis(ys, 0, 1)
        staged = states                                # leaves (K, B, …)
    if cfg.use_post_norm:
        y = rmsnorm(y, p["post1"], cfg.norm_eps)
    h = h + y
    if bc.ffn != "none":
        x = rmsnorm(h, p["norm2"], cfg.norm_eps)
        if bc.ffn == "moe":
            B, K, D = x.shape
            y = moe_mod.moe_decode(cfg, p["moe"], x.reshape(B * K, D),
                                   ctx).reshape(B, K, D)
        else:
            y = mlp(cfg, p["mlp"], x, ctx)
        if cfg.use_post_norm:
            y = rmsnorm(y, p["post2"], cfg.norm_eps)
        h = h + y
    return h, staged


def decode_verify(cfg: ModelConfig, params, cache, tokens, pos0,
                  ctx: ShardCtx, page_table=None, paged_kernel=True):
    """Speculative verify pass. tokens (B,K) = [last committed token,
    proposals g_1..g_{K-1}]; pos0 (B,) the write position of tokens[:,0].
    → (logits (B,K,V) f32, staged tree). logits[:, j] is the target's
    next-token distribution after consuming tokens[:, :j+1] — exactly what
    K serial `decode_step`s would produce, in one batched pass. The cache
    is read-only; `decode_commit` writes the accepted prefix."""
    segments = layer_schedule(cfg)
    h = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.pdtype)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    h = ctx.constrain(h, ("batch", None, None))
    staged_blocks = []
    for seg, sp, sc in zip(segments, params["blocks"], cache["blocks"]):

        def body(hc, xs, seg=seg):
            slot_params, slot_cache = xs
            stg = {}
            for j, bc in enumerate(seg.pattern):
                hc, s = block_verify(cfg, bc, slot_params[f"s{j}"],
                                     slot_cache[f"s{j}"], hc, pos0, ctx,
                                     page_table=page_table,
                                     paged_kernel=paged_kernel)
                stg[f"s{j}"] = s
            return hc, stg

        h, stg = jax.lax.scan(body, h, (sp, sc))
        staged_blocks.append(stg)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    w = (params["embed"]["table"].T if cfg.tie_embeddings
         else params["unembed"]["w"])
    logits = jnp.einsum("bkd,dv->bkv", h, w.astype(h.dtype),
                        preferred_element_type=F32)
    logits = _softcap(logits, cfg.final_softcap)
    logits = ctx.constrain(logits, ("batch", None, "vocab"))
    return logits, {"blocks": staged_blocks}


# -------------------------------------------------- multi-token KV commit
def commit_rows(cache, rows, pos0, n, ctx: ShardCtx, *, window: int = 0,
                axes, page_table=None):
    """Write the accepted prefix of staged `rows` (B,K,…) into one
    attention cache leaf: row j lands at absolute position pos0+j for
    j < n (B,). Dense leaves use the same shard-local masked writes as the
    serial loop (ring addressing for windows); paged leaves route each row
    through the page table, with rejected rows (j ≥ n) deflected to the
    trash page 0 exactly like a frozen slot's scribble. `axes` is the
    leaf's logical-axis tuple (the caller knows the layout)."""
    mesh = ctx.mesh
    K = rows.shape[1]
    msize = ctx.axis_size("model")
    bp = ctx.spec(("batch",) + (None,) * (rows.ndim - 1), rows.shape)[0]
    rspec = P(*((bp,) + (None,) * (rows.ndim - 1)))
    pspec = P(bp)

    if page_table is not None:
        _check_paged_args(page_table, pos0, window=window)
        poolspec = ctx.spec(axes, cache.shape)
        ptspec = P(bp, None)

        def local(pool, rows, pt, pos0, n):
            i = jax.lax.axis_index("model")
            T, ps = pt.shape[1], pool.shape[1] * msize
            for j in range(K):
                # rejected rows route to the trash page (pos ≥ T·ps)
                pos = jnp.where(j < n, pos0 + j, T * ps)
                pool = _paged_write(pool, rows[:, j], pt, pos, i, msize)
            return pool

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(poolspec, rspec, ptspec, pspec, pspec),
                           out_specs=poolspec, check_vma=False)
        return fn(cache, rows, page_table, pos0, n)

    cspec = ctx.spec(axes, cache.shape)

    def local(cache, rows, pos0, n):
        i = jax.lax.axis_index("model")
        S_loc = cache.shape[1]
        S_tot = S_loc * msize
        for j in range(K):
            pos = pos0 + j
            wpos = pos % S_tot if window else pos
            rel = jnp.where(j < n, wpos - i * S_loc, -1)
            cache = _local_write(cache, rows[:, j], rel)
        return cache

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(cspec, rspec, pspec, pspec),
                       out_specs=cspec, check_vma=False)
    return fn(cache, rows, pos0, n)


def _commit_scan_state(cache, states, n):
    """Mamba leaves: `states` (K,B,…) are the K post-step states staged by
    `block_verify`; keep state n-1 per batch row (n = 0 → the pre-verify
    state, i.e. nothing advanced)."""
    def sel(c, s):
        full = jnp.concatenate([c[None], s.astype(c.dtype)], axis=0)
        return full[n, jnp.arange(c.shape[0])]
    return jax.tree.map(sel, cache, states)


def block_commit(cfg: ModelConfig, bc, cache, staged, pos0, n,
                 ctx: ShardCtx, page_table=None):
    if bc.mixer != "attn":
        return _commit_scan_state(cache, staged, n)
    pt = None if bc.window else page_table
    if cfg.mla:
        axes = ((None, "kv_seq", None) if pt is not None
                else ("batch", "kv_seq", None))
        return {"ckv": commit_rows(cache["ckv"], staged["ckv"], pos0, n,
                                   ctx, window=bc.window, axes=axes,
                                   page_table=pt)}
    axes = ((None, "kv_seq", "kv_heads", None) if pt is not None
            else ("batch", "kv_seq", "kv_heads", None))
    return {"k": commit_rows(cache["k"], staged["k"], pos0, n, ctx,
                             window=bc.window, axes=axes, page_table=pt),
            "v": commit_rows(cache["v"], staged["v"], pos0, n, ctx,
                             window=bc.window, axes=axes, page_table=pt)}


def decode_commit(cfg: ModelConfig, cache, staged, pos0, n, ctx: ShardCtx,
                  page_table=None):
    """Commit half of the verify/commit split: write the first n (B,)
    staged rows/states into the cache. Positions pos0..pos0+n-1 receive
    the K/V of the accepted verify *inputs*; the correction token is NOT
    written — it becomes the next round's tokens[:,0] and its row is
    staged (and committed) by the next verify."""
    new_blocks = []
    for seg, sc, st in zip(layer_schedule(cfg), cache["blocks"],
                           staged["blocks"]):

        def body(c, xs, seg=seg):
            slot_cache, slot_staged = xs
            out = {}
            for j, bc in enumerate(seg.pattern):
                out[f"s{j}"] = block_commit(cfg, bc, slot_cache[f"s{j}"],
                                            slot_staged[f"s{j}"], pos0, n,
                                            ctx, page_table=page_table)
            return c, out

        _, new_sc = jax.lax.scan(body, 0, (sc, st))
        new_blocks.append(new_sc)
    return {"blocks": new_blocks}


# --------------------------------------------- acceptance / emission law
def spec_candidates(proposals, corrections, accept, active, remaining,
                    pos0, *, eos_id: int, max_len: int):
    """The pure emission law of one speculative round (unit-testable).

    proposals (B,k): draft tokens g_1..g_k. corrections (B,k+1): the
    target's fallback token at each acceptance depth (argmax in greedy
    mode, residual/bonus sample otherwise; index k is the bonus). accept
    (B,k): per-proposal verifier verdicts. active/remaining/pos0 (B,): the
    slot state entering the round.

    Returns (cand (B,K), emit (B,K) bool, n (B,), m (B,)) with K = k+1:
    m = accepted prefix length = Σ cumprod(accept); cand[j] = g_{j+1} for
    j < m else corrections[m]; emit marks the emitted prefix after EOS /
    token-budget / max_len truncation — exactly the prefix the serial loop
    would have emitted over its next n = emit.sum() steps (the still-active
    law `active & (remaining>0) & (tok≠eos) & (pos<max_len-1)` applied
    cumulatively), which is what makes greedy spec-decode token-identical
    to target-only decoding."""
    B, k = proposals.shape
    K = k + 1
    m = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
    x = jnp.take_along_axis(corrections, m[:, None], axis=1)[:, 0]
    g_pad = jnp.concatenate(
        [proposals, jnp.zeros((B, 1), proposals.dtype)], axis=1)
    jj = jnp.arange(K)[None]
    cand = jnp.where(jj < m[:, None], g_pad, x[:, None])
    prev = jnp.concatenate(
        [jnp.full((B, 1), -1, cand.dtype), cand[:, :-1]], axis=1)
    cond = (jj <= m[:, None]) & (prev != eos_id) & \
        (remaining[:, None] > jj) & (pos0[:, None] + jj < max_len - 1)
    # the first token is the serial loop's unconditional step: an active
    # slot always emits at least one token per round
    cond = jnp.concatenate([jnp.ones((B, 1), bool), cond[:, 1:]], axis=1)
    emit = active[:, None] & (jnp.cumprod(cond.astype(jnp.int32), 1) > 0)
    n = jnp.sum(emit.astype(jnp.int32), axis=1)
    return cand, emit, n, m


def spec_decode_loop(cfg: ModelConfig, draft_cfg: ModelConfig, params,
                     draft_params, cache, draft_cache, tokens, pos, active,
                     remaining, ctx: ShardCtx, *, spec_k: int,
                     num_steps: int, eos_id: int, max_len: int,
                     page_table=None, paged_kernel=True,
                     temperature: float = 0.0, top_k: int = 0,
                     top_p: float = 0.0, rng=None):
    """Speculative decode quantum: each scan step runs `spec_k` serial
    draft steps plus ONE batched target verify, emitting up to spec_k+1
    tokens per slot per round.

    Greedy (temperature=0): a proposal is accepted iff it equals the
    target argmax at its depth and corrections are target argmaxes, so the
    emitted stream is token-identical to the serial loop. Sampled:
    Leviathan/Chen rejection sampling against the *processed*
    (temperature/top-k/top-p) distributions p and q — accept g with
    probability min(1, p(g)/q(g)), on rejection at depth i resample from
    the residual norm(max(p_i - q_i, 0)), and after k acceptances draw the
    bonus token from p_k — which preserves the target-only sampling law.

    The draft writes its dense cache optimistically at pos..pos+k-1; rows
    beyond the accepted prefix are stale, but the draft is validated to be
    full-attention/dense-only (validity gpos ≤ pos), so a stale row is
    always overwritten by the next round's step at that position before it
    ever becomes attendable. The target cache is never written by verify;
    `decode_commit` writes exactly the accepted prefix.

    Returns ((caches, tokens, pos, active, remaining, rng), toks, msks,
    acc) where caches = {"tgt", "dft"}, toks/msks are (num_steps, K, B) in
    emission order and acc (num_steps, B) counts accepted proposals."""
    K = spec_k + 1
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def body(carry, _):
        tcache, dcache, tokens, pos, active, remaining, key = carry

        def dbody(dc, _):
            dcache, dtok, dpos, dkey = dc
            dlogits, dcache = decode_step(draft_cfg, draft_params, dcache,
                                          dtok, dpos, ctx)
            if temperature:
                dkey, sub = jax.random.split(dkey)
                fl = _filter_logits(dlogits, temperature=temperature,
                                    top_k=top_k, top_p=top_p)
                g = jax.random.categorical(sub, fl, -1).astype(jnp.int32)
                q = jax.nn.softmax(fl, axis=-1)
            else:
                g = jnp.argmax(dlogits, -1).astype(jnp.int32)
                q = jnp.zeros((dlogits.shape[0], 0), F32)      # unused
            return (dcache, g, dpos + 1, dkey), (g, q)

        (dcache, _, _, key), (g, qp) = jax.lax.scan(
            dbody, (dcache, tokens, pos, key), None, length=spec_k)
        gT = jnp.moveaxis(g, 0, 1)                             # (B, k)

        vt = jnp.concatenate([tokens[:, None], gT], axis=1)    # (B, K)
        logits, staged = decode_verify(cfg, params, tcache, vt, pos, ctx,
                                       page_table=page_table,
                                       paged_kernel=paged_kernel)

        if temperature:
            key, k_acc, k_res = jax.random.split(key, 3)
            fl = _filter_logits(logits, temperature=temperature,
                                top_k=top_k, top_p=top_p)
            pp = jax.nn.softmax(fl, axis=-1)                   # (B, K, V)
            qT = jnp.moveaxis(qp, 0, 1)                        # (B, k, V)
            p_at = jnp.take_along_axis(pp[:, :spec_k], gT[..., None],
                                       axis=-1)[..., 0]
            q_at = jnp.take_along_axis(qT, gT[..., None], axis=-1)[..., 0]
            u = jax.random.uniform(k_acc, gT.shape, F32)
            accept = u * q_at < p_at           # u < p/q without the divide
            r = jnp.maximum(pp[:, :spec_k] - qT, 0.0)
            rsum = jnp.sum(r, -1, keepdims=True)
            r = jnp.where(rsum > 0.0, r, pp[:, :spec_k])   # p ≡ q → use p
            resid = jnp.concatenate([r, pp[:, spec_k:]], axis=1)
            corrections = jax.random.categorical(
                k_res, jnp.log(resid + 1e-30), axis=-1).astype(jnp.int32)
        else:
            corrections = jnp.argmax(logits, -1).astype(jnp.int32)
            accept = gT == corrections[:, :spec_k]

        cand, emit, n, m = spec_candidates(gT, corrections, accept, active,
                                           remaining, pos, eos_id=eos_id,
                                           max_len=max_len)
        tcache = decode_commit(cfg, tcache, staged, pos, n, ctx,
                               page_table=page_table)
        emit_tok = jnp.where(emit, cand, -1)
        remaining = remaining - n.astype(remaining.dtype)
        pos = pos + n.astype(pos.dtype)
        last = jnp.take_along_axis(cand, jnp.maximum(n - 1, 0)[:, None],
                                   axis=1)[:, 0]
        still = active & (remaining > 0) & (last != eos_id) & \
            (pos < max_len - 1)
        tokens = jnp.where(still, last, tokens)
        acc = jnp.where(active, m, 0).astype(jnp.int32)
        carry = (tcache, dcache, tokens, pos, still, remaining, key)
        return carry, (emit_tok.T, emit.T, acc)

    carry = (cache, draft_cache, tokens, pos, active, remaining, rng)
    carry, (toks, msks, acc) = jax.lax.scan(body, carry, None,
                                            length=num_steps)
    tcache, dcache, tokens, pos, active, remaining, key = carry
    carry = ({"tgt": tcache, "dft": dcache}, tokens, pos, active,
             remaining, key)
    return carry, toks, msks, acc


def decode_loop_fn(cfg: ModelConfig, ctx: ShardCtx, *, num_steps: int,
                   eos_id: int, max_len: int, paged: bool = False,
                   paged_kernel=True, temperature: float = 0.0,
                   top_k: int = 0, top_p: float = 0.0,
                   draft_cfg: ModelConfig | None = None, spec_k: int = 0):
    """Engine-facing closure, shaped for jit(donate_argnums=(1,…,6)).

    Returns (carry, packed) where `packed` is one (2·num_steps + 1, B) int32
    array — emitted tokens, emission masks, then the post-quantum `active`
    vector — so the engine's quantum costs exactly ONE blocking host fetch
    (three separate fetches would sync the pipe three times). The PRNG key
    is carry slot 5, donated and device-resident like the rest. In paged
    mode the loop takes the (B,T) page table as a trailing, non-donated
    arg; the engine passes only the table's *live* prefix (bucketed), which
    is what lets the kernel path skip dead pages wholesale.

    `draft_cfg` + `spec_k` switch the quantum to the speculative loop:
    `params`/`cache` become {"tgt", "dft"} trees, each round emits up to
    spec_k+1 tokens, and `packed` grows to
    (2·num_steps·(spec_k+1) + num_steps + 1, B) — emitted tokens, emission
    masks (both round-major in emission order), per-round accepted-proposal
    counts, then `active`. Still exactly ONE host fetch per quantum."""

    if draft_cfg is not None:
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1 with a draft, got "
                             f"{spec_k}")
        NK = num_steps * (spec_k + 1)

        def _pack_spec(carry, toks, msks, acc):
            active = carry[3]
            B = active.shape[0]
            return carry, jnp.concatenate(
                [toks.reshape(NK, B), msks.astype(jnp.int32).reshape(NK, B),
                 acc, active[None].astype(jnp.int32)], axis=0)

        if paged:
            def loop(params, cache, tokens, pos, active, remaining, rng,
                     page_table):
                carry, toks, msks, acc = spec_decode_loop(
                    cfg, draft_cfg, params["tgt"], params["dft"],
                    cache["tgt"], cache["dft"], tokens, pos, active,
                    remaining, ctx, spec_k=spec_k, num_steps=num_steps,
                    eos_id=eos_id, max_len=max_len, page_table=page_table,
                    paged_kernel=paged_kernel, temperature=temperature,
                    top_k=top_k, top_p=top_p, rng=rng)
                return _pack_spec(carry, toks, msks, acc)
            return loop

        def loop(params, cache, tokens, pos, active, remaining, rng):
            carry, toks, msks, acc = spec_decode_loop(
                cfg, draft_cfg, params["tgt"], params["dft"],
                cache["tgt"], cache["dft"], tokens, pos, active,
                remaining, ctx, spec_k=spec_k, num_steps=num_steps,
                eos_id=eos_id, max_len=max_len, paged_kernel=paged_kernel,
                temperature=temperature, top_k=top_k, top_p=top_p, rng=rng)
            return _pack_spec(carry, toks, msks, acc)
        return loop

    def _pack(carry, toks, msks):
        active = carry[3]
        return carry, jnp.concatenate(
            [toks, msks.astype(jnp.int32), active[None].astype(jnp.int32)],
            axis=0)

    if paged:
        def loop(params, cache, tokens, pos, active, remaining, rng,
                 page_table):
            carry, toks, msks = decode_loop(
                cfg, params, cache, tokens, pos, active, remaining, ctx,
                num_steps=num_steps, eos_id=eos_id, max_len=max_len,
                page_table=page_table, paged_kernel=paged_kernel,
                temperature=temperature, top_k=top_k, top_p=top_p, rng=rng)
            return _pack(carry, toks, msks)
        return loop

    def loop(params, cache, tokens, pos, active, remaining, rng):
        carry, toks, msks = decode_loop(
            cfg, params, cache, tokens, pos, active, remaining, ctx,
            num_steps=num_steps, eos_id=eos_id, max_len=max_len,
            temperature=temperature, top_k=top_k, top_p=top_p, rng=rng)
        return _pack(carry, toks, msks)

    return loop


# ---------------------------------------------------- whisper decode step
def whisper_decode_step(cfg: ModelConfig, params, cache, tokens, pos,
                        ctx: ShardCtx):
    """Decoder step against per-layer self cache + prefilled cross KV."""
    h = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.pdtype)
    h = h + jnp.take(params["dec_pos"],
                     jnp.clip(pos, 0, cfg.max_decoder_len - 1), axis=0)
    h = ctx.constrain(h, ("batch", None))
    G = cfg.n_heads // cfg.n_kv_heads

    def body(hc, xs):
        p, c = xs
        B = hc.shape[0]
        x = rmsnorm(hc, p["norm1"], cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", x, p["self_attn"]["wq"])
        k = jnp.einsum("bd,dhk->bhk", x, p["self_attn"]["wk"])
        v = jnp.einsum("bd,dhk->bhk", x, p["self_attn"]["wv"])
        qg = q.reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
        o, ck, cv = flash_decode_gqa(qg, k, v, c["k"], c["v"], pos, window=0,
                                     scale=cfg.head_dim ** -0.5, softcap=0.0,
                                     ctx=ctx)
        o = jnp.einsum("bk,kd->bd", o.reshape(B, -1),
                       p["self_attn"]["wo"].reshape(-1, cfg.d_model))
        hc = hc + ctx.constrain(o, ("batch", None))
        # cross attention against the (static) prefilled cross KV
        x = rmsnorm(hc, p["norm_x"], cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", x, p["cross"]["wq"])
        qg = q.reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
        enc_len = jnp.full((B,), c["xk"].shape[1] - 1, jnp.int32)
        o, _, _ = flash_decode_gqa(qg, jnp.zeros_like(k), jnp.zeros_like(v),
                                   c["xk"], c["xv"],
                                   enc_len, window=0,
                                   scale=cfg.head_dim ** -0.5, softcap=0.0,
                                   ctx=ctx, update=False)
        o = jnp.einsum("bk,kd->bd", o.reshape(B, -1),
                       p["cross"]["wo"].reshape(-1, cfg.d_model))
        hc = hc + ctx.constrain(o, ("batch", None))
        x = rmsnorm(hc, p["norm2"], cfg.norm_eps)
        hc = hc + mlp(cfg, p["mlp"], x[:, None], ctx)[:, 0]
        return hc, {"k": ck, "v": cv, "xk": c["xk"], "xv": c["xv"]}

    h, new_dec = jax.lax.scan(body, h, (params["dec_blocks"],
                                        cache["dec_blocks"]))
    h = rmsnorm(h, params["dec_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", h,
                        params["embed"]["table"].T.astype(h.dtype),
                        preferred_element_type=F32)
    logits = ctx.constrain(logits, ("batch", "vocab"))
    return logits, {"dec_blocks": new_dec}


def serve_step_fn(cfg: ModelConfig, ctx: ShardCtx):
    fn = whisper_decode_step if cfg.enc_dec else decode_step

    def step(params, cache, tokens, pos):
        return fn(cfg, params, cache, tokens, pos, ctx)

    return step


# --------------------------------------------------- resume-from-emitted
def plan_resume(prompt, out, max_new: int, eos_id: int = -1):
    """Retry law for a stream reclaimed from a failed tier (DESIGN.md §8).

    Returns ``(resume_prompt, remaining_new)`` — the prompt to re-prefill
    and the decode budget left — or ``None`` when the stream is already
    terminal (budget spent, or the last emitted token is EOS) and needs no
    retry.

    Why the recovery is token-identical for greedy traffic: the emitted
    prefix was produced by causal decoding, so the model's distribution
    for token ``len(out)+1`` depends only on ``prompt + out`` — exactly
    the context a fresh prefill of ``resume_prompt`` scores. This is the
    same read-only-cache discipline the speculative verify path relies on
    (§7: verify scores positions against cache + staged rows without
    writing), applied across engines instead of within a quantum: the
    failed tier's cache is *garbage* after a fault, so instead of trusting
    it we rebuild the identical context from the tokens the host already
    holds. At ``temperature=0`` the continuation therefore equals what the
    unfailed stream would have produced byte-for-byte; sampled traffic
    resumes the same law but not the same draws (the PRNG position is not
    part of a request's identity).
    """
    emitted = len(out)
    if emitted >= max_new:
        return None                       # budget already spent
    if eos_id >= 0 and emitted and out[-1] == eos_id:
        return None                       # stream ended at EOS
    return list(prompt) + list(out), max_new - emitted
