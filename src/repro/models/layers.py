"""Shared layer primitives: norms, rope, activations, dense MLP, embedding,
and the chunked (memory-bounded) cross-entropy loss.

Sharding conventions (DESIGN.md §3):
  residual stream   (B, S, D)  →  ("batch", "seq", None)    seq-sharded (SP)
  attention / mlp internals     →  heads / mlp hidden over "model"
All constraints go through ``ShardCtx`` so a 1-device mesh is a no-op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.axes import ShardCtx
from repro.sharding.params import pd

F32 = jnp.float32


# ----------------------------------------------------------------- norms
def rmsnorm_def(dim: int):
    return pd((dim,), (None,), init="ones", dtype=jnp.float32)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w).astype(dt)


# ------------------------------------------------------------------ rope
def rope_tables(positions: jax.Array, dim: int, theta: float):
    """positions (…,) int → cos/sin (…, dim/2) fp32."""
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, S, H, dh); cos/sin (S, dh/2) or (B, S, dh/2). NeoX half-rotation."""
    dt = x.dtype
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    if cos.ndim == 2:  # (S, half) → broadcast over batch & heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:              # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(dt)


# ------------------------------------------------------------ activations
def activation(name: str):
    if name == "swiglu" or name == "geglu":
        raise ValueError("gated activations handled inside mlp()")
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


def is_gated(act: str) -> bool:
    return act in ("swiglu", "geglu")


def gate_fn(act: str):
    return jax.nn.silu if act == "swiglu" else (
        lambda x: jax.nn.gelu(x, approximate=True))


# ------------------------------------------------------------- dense MLP
def mlp_defs(cfg: ModelConfig, d_ff: int, n_layers_hint: int = 1):
    out_scale = 0.02 / max(1.0, (2 * max(cfg.n_layers, 1)) ** 0.5)
    d = {"w_up": pd((cfg.d_model, d_ff), ("embed", "mlp"), dtype=cfg.pdtype),
         "w_down": pd((d_ff, cfg.d_model), ("mlp", "embed"), scale=out_scale,
                      dtype=cfg.pdtype)}
    if is_gated(cfg.act):
        d["w_gate"] = pd((cfg.d_model, d_ff), ("embed", "mlp"), dtype=cfg.pdtype)
    return d


def _gather_fsdp(x, spec, keep=("model",)):
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in reversed(axes):
            if ax not in keep:
                x = jax.lax.all_gather(x, ax, axis=dim, tiled=True)
    return x


def mlp(cfg: ModelConfig, p, x: jax.Array, ctx: ShardCtx,
        d_ff: int | None = None) -> jax.Array:
    """x (B, S, D) seq-sharded → (B, S, D) seq-sharded.

    Megatron column/row TP + sequence parallelism with *explicit* shard_map
    collectives: all-gather(bf16 x) → local matmuls → psum-scatter(bf16).
    (GSPMD left to its own devices hoists the gather into the fp32 norm
    internals and emits all-reduce instead of reduce-scatter — measured 3×
    wire overhead; EXPERIMENTS.md §Perf iteration 3.)"""
    if ctx.axis_size("model") == 1:
        h = jnp.einsum("bsd,df->bsf", x, p["w_up"])
        if is_gated(cfg.act):
            h = gate_fn(cfg.act)(jnp.einsum("bsd,df->bsf", x, p["w_gate"])) * h
        else:
            h = activation(cfg.act)(h)
        return jnp.einsum("bsf,fd->bsd", h, p["w_down"])

    xspec = ctx.spec(("batch", "seq", None), x.shape)
    seq_sharded = xspec[1] is not None      # decode S=1 → replicated path
    pspecs = {n: ctx.spec(("embed", "mlp") if n != "w_down" else
                          ("mlp", "embed"), p[n].shape) for n in p}

    def local(x_loc, params):
        xg = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True) \
            if seq_sharded else x_loc
        w_up = _gather_fsdp(params["w_up"], pspecs["w_up"])
        h = jnp.einsum("bsd,df->bsf", xg, w_up)
        if is_gated(cfg.act):
            w_gate = _gather_fsdp(params["w_gate"], pspecs["w_gate"])
            h = gate_fn(cfg.act)(jnp.einsum("bsd,df->bsf", xg, w_gate)) * h
        else:
            h = activation(cfg.act)(h)
        w_down = _gather_fsdp(params["w_down"], pspecs["w_down"])
        out = jnp.einsum("bsf,fd->bsd", h, w_down)
        if seq_sharded:
            return jax.lax.psum_scatter(out, "model", scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(out, "model")

    fn = jax.shard_map(local, mesh=ctx.mesh,
                       in_specs=(xspec, {n: pspecs[n] for n in p}),
                       out_specs=xspec, check_vma=False)
    return fn(x, dict(p))


# -------------------------------------------------------------- embedding
def embed_defs(cfg: ModelConfig):
    d = {"table": pd((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                     scale=0.02, dtype=cfg.pdtype)}
    if cfg.frontend != "none" and cfg.frontend_dim:
        d["frontend_proj"] = pd((cfg.frontend_dim, cfg.d_model),
                                ("frontend", "embed"), dtype=cfg.pdtype)
    return d


def embed(cfg: ModelConfig, p, tokens: jax.Array, ctx: ShardCtx,
          frontend_embed: jax.Array | None = None) -> jax.Array:
    """tokens (B, S) → (B, S, D). VLM: first `frontend_tokens` positions are
    replaced by projected patch embeddings (tokens there are a pad id)."""
    h = jnp.take(p["table"], tokens, axis=0).astype(cfg.pdtype)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    if frontend_embed is not None:
        fe = jnp.einsum("bfe,ed->bfd", frontend_embed.astype(cfg.pdtype),
                        p["frontend_proj"])
        if cfg.embed_scale:
            fe = fe * jnp.asarray(cfg.d_model ** 0.5, fe.dtype)
        h = jnp.concatenate([fe, h[:, fe.shape[1]:, :]], axis=1)
    return ctx.constrain(h, ("batch", "seq", None))


# ------------------------------------------------- unembed + chunked loss
def unembed_defs(cfg: ModelConfig):
    if cfg.tie_embeddings:
        return {}
    return {"w": pd((cfg.d_model, cfg.vocab), ("embed", "vocab"),
                    dtype=cfg.pdtype)}


def _softcap(x, cap):
    return jnp.tanh(x / cap) * cap if cap else x


def logits_fn(cfg: ModelConfig, embed_p, unembed_p, h, ctx: ShardCtx):
    """h (B, T, D) → logits (B, T, V) fp32, vocab-sharded."""
    w = embed_p["table"].T if cfg.tie_embeddings else unembed_p["w"]
    logits = jnp.einsum("btd,dv->btv", h, w.astype(h.dtype),
                        preferred_element_type=F32)
    logits = _softcap(logits, cfg.final_softcap)
    return ctx.constrain(logits, ("batch", None, "vocab"))


def chunked_ce_loss(cfg: ModelConfig, embed_p, unembed_p, h, targets, mask,
                    ctx: ShardCtx, chunk: int = 512):
    """Cross-entropy without materialising (B, S, V).

    h (B, S, D) seq-sharded. Scans over sequence chunks; logits stay
    vocab-sharded; the label logit is extracted with a sharded one-hot
    contraction (no cross-shard gather). Returns (sum_loss, sum_count).
    """
    h = ctx.constrain(h, ("batch", None, None))  # all-gather seq for chunking
    B, S, D = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk

    def chunk_loss(hc, tc, mc):
        logits = logits_fn(cfg, embed_p, unembed_p, hc, ctx)     # (B,c,V) f32
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.squeeze(m, -1) + jnp.log(
            jnp.sum(jnp.exp(logits - m), axis=-1))
        onehot = jax.nn.one_hot(tc, cfg.vocab, dtype=logits.dtype)
        onehot = ctx.constrain(onehot, ("batch", None, "vocab"))
        lab = jnp.sum(logits * onehot, axis=-1)
        loss = (lse - lab) * mc
        return jnp.sum(loss), jnp.sum(mc)

    chunk_loss = jax.checkpoint(chunk_loss)

    def body(carry, xs):
        sl, sc = carry
        l, c = chunk_loss(*xs)
        return (sl + l, sc + c), None

    hs = h[:, :n * chunk].reshape(B, n, chunk, D).swapaxes(0, 1)
    ts = targets[:, :n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
    ms = mask[:, :n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
    (sum_l, sum_c), _ = jax.lax.scan(
        body, (jnp.zeros((), F32), jnp.zeros((), F32)), (hs, ts, ms))
    if rem:
        l, c = chunk_loss(h[:, n * chunk:], targets[:, n * chunk:],
                          mask[:, n * chunk:])
        sum_l, sum_c = sum_l + l, sum_c + c
    return sum_l, sum_c
