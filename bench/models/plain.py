"""The plain Llama/Mistral block: RMSNorm, grouped-query attention with
rotary embeddings (half rotation, as the Hugging Face code applies them),
causal masking with an optional sliding window, a SwiGLU feed-forward.

The module's interface is in ``bench/models/__init__.py``.
``program_config`` and ``params_fn`` import the program when called; the
reference's block (``layer_weights``, ``layer``, ``final_norm``) and the
counts import nothing of it.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import HI, Q_CHUNK, act, quantize
from bench.weights import draw, shared_draw, vocab_blocks

# program config field <- published key of the configuration file
FIELDS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
          "d_ff": "intermediate_size", "vocab": "vocab_size",
          "n_layers": "num_hidden_layers", "rope_theta": "rope_theta",
          "norm_eps": "rms_norm_eps", "sliding_window": "sliding_window"}
# what the reference implements; any other setting would differ from it
PLAIN = {"act": "swiglu", "use_post_norm": False, "use_rope": True,
         "attn_softcap": 0.0, "final_softcap": 0.0, "moe": None, "mla": None,
         "ssm": None, "enc_dec": False, "tie_embeddings": False,
         "embed_scale": False, "frontend": "none", "local_global_period": 0}
# the block's leaves -> draw ids (ids are data: append only)
LEAF_IDS = {"attn_norm": 4, "wq": 5, "wk": 6, "wv": 7, "wo": 8,
            "mlp_norm": 9, "w_gate": 10, "w_up": 11, "w_down": 12}
LAYER_LEAVES = tuple(LEAF_IDS)
NORMS = ("attn_norm", "mlp_norm")
# axes each matmul weight is contracted over (its input side)
CONTRACTED = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
              "w_gate": (0,), "w_up": (0,), "w_down": (0,)}
# program leaf path inside one block -> leaf of LEAF_IDS
BLOCK_LEAVES = {("norm1",): "attn_norm", ("attn", "wq"): "wq",
                ("attn", "wk"): "wk", ("attn", "wv"): "wv",
                ("attn", "wo"): "wo", ("norm2",): "mlp_norm",
                ("mlp", "w_gate"): "w_gate", ("mlp", "w_up"): "w_up",
                ("mlp", "w_down"): "w_down"}
# the paged decode attention kernel's operation names in a device trace
ATTENTION_KERNEL = r"^paged_flash_decode_gqa\b"
KV_BYTES = 2             # keys, values and activations are bf16, as served
ACT_BYTES = 2


def model_shape(conf: dict) -> dict:
    """The published sizes (as run) that the reference and counts read."""
    return {k: conf[k] for k in FIELDS.values()}


def program_config(conf: dict):
    """The registered config with only its depth set from the file, after
    checking that every other size and setting is the file's."""
    from repro.configs import get_config
    cfg = get_config(conf["registry"])
    m = conf
    cfg = dataclasses.replace(cfg, n_layers=m["num_hidden_layers"])
    for field, key in FIELDS.items():
        want = m[key] or 0 if key == "sliding_window" else m[key]
        if getattr(cfg, field) != want:
            raise ValueError(f"{conf['registry']}: {field} is "
                             f"{getattr(cfg, field)!r}, the file says "
                             f"{key}={m[key]!r}")
    for field, want in PLAIN.items():
        if getattr(cfg, field) != want:
            raise ValueError(f"{conf['registry']}: {field}="
                             f"{getattr(cfg, field)!r} is not what the "
                             f"plain reference implements ({want!r})")
    if cfg.param_dtype != "bfloat16":
        raise ValueError(f"{conf['registry']} serves {cfg.param_dtype}")
    return cfg


def leaf_shape(shape: dict, name: str) -> tuple[int, ...]:
    """One layer's slice of a block leaf."""
    D, H, K, dh = (shape["hidden_size"], shape["num_attention_heads"],
                   shape["num_key_value_heads"], shape["head_dim"])
    F = shape["intermediate_size"]
    return {"attn_norm": (D,), "mlp_norm": (D,), "wq": (D, H, dh),
            "wk": (D, K, dh), "wv": (D, K, dh), "wo": (H, dh, D),
            "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}[name]


def leaf_std(shape: dict, name: str) -> float | None:
    """The leaf's standard deviation; None for a norm gain."""
    if name in NORMS:
        return None
    if name in ("wo", "w_down"):
        return 0.02 / max(1.0, (2 * shape["num_hidden_layers"]) ** 0.5)
    return 0.02


def block_draw(root, shape: dict, name: str, layer) -> jax.Array:
    """Layer ``layer`` (may be traced) of the block leaf ``name``."""
    return draw(root, LEAF_IDS[name], layer, leaf_shape(shape, name),
                leaf_std(shape, name))


def _block_tree(defs, fn, path=()):
    if isinstance(defs, dict):
        return {k: _block_tree(v, fn, path + (k,)) for k, v in defs.items()}
    return fn(path, defs)


def params_fn(cfg, shape: dict):
    """``root key -> parameter tree`` in the program's layout, every leaf
    a stack or concatenation of ``bench.weights`` draws."""
    from repro.models.model import model_defs
    from repro.models.transformer import layer_schedule
    from repro.sharding import params as prm
    defs = model_defs(cfg)
    segments = layer_schedule(cfg)
    if set(defs) != {"embed", "blocks", "final_norm", "unembed"} or set(
            defs["embed"]) != {"table"} or set(defs["unembed"]) != {"w"}:
        raise ValueError(f"unexpected parameter tree: {sorted(defs)}")
    blocks = vocab_blocks(shape["vocab_size"])

    def build(root):
        out = {
            "embed": {"table": jnp.concatenate(
                [shared_draw(root, shape, "embed", b, n)
                 for b, (_, n) in enumerate(blocks)], 0)},
            "unembed": {"w": jnp.concatenate(
                [shared_draw(root, shape, "unembed", b, n)
                 for b, (_, n) in enumerate(blocks)], 1)},
            "final_norm": shared_draw(root, shape, "final_norm"),
            "blocks": []}
        base = 0
        for seg, seg_defs in zip(segments, defs["blocks"]):
            plen = len(seg.pattern)
            slot = {}
            for j in range(plen):
                def leaf(path, d, j=j, base=base, plen=plen, seg=seg):
                    if path not in BLOCK_LEAVES:
                        raise ValueError(f"unexpected block leaf {path}")
                    return jnp.stack(
                        [block_draw(root, shape, BLOCK_LEAVES[path],
                                    base + r * plen + j)
                         for r in range(seg.repeat)])
                slot[f"s{j}"] = _block_tree(seg_defs[f"s{j}"], leaf)
            out["blocks"].append(slot)
            base += plen * seg.repeat

        def cast(x, d):
            if x.shape != d.shape:
                raise ValueError(f"shape {x.shape} != program's {d.shape}")
            return x.astype(d.dtype)
        return jax.tree.map(cast, out, defs, is_leaf=prm.is_def)
    return build, defs


# ---- the reference's block ------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, heads, dh); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit,
         static_argnames=("H", "K", "theta", "eps", "window", "kind"))
def _layer(h, w, *, H, K, theta, eps, window, kind=None):
    """One block over one padded sequence h (S, D) float32. With ``kind``
    (a control) every matmul input and the keys and values are rounded to
    that precision as well as the weights."""
    S = h.shape[0]
    pos = jnp.arange(S)
    x = act(_rms(h, w["attn_norm"], eps), kind)
    q = _rope(jnp.einsum("sd,dhk->shk", x, w["wq"], precision=HI), pos,
              theta)
    k = act(_rope(jnp.einsum("sd,dhk->shk", x, w["wk"], precision=HI),
                  pos, theta), kind)
    v = act(jnp.einsum("sd,dhk->shk", x, w["wv"], precision=HI), kind)
    dh = q.shape[-1]
    G = H // K
    k = jnp.repeat(k, G, axis=1)            # head i reads kv head i // G
    v = jnp.repeat(v, G, axis=1)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_CHUNK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * dh ** -0.5
        i = q0 + jnp.arange(Q_CHUNK)[:, None]
        j = jnp.arange(S)[None, :]
        ok = j <= i
        if window:
            ok &= j > i - window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, S, Q_CHUNK))
    o = act(o.reshape(S, H * dh), kind).reshape(S, H, dh)
    h = h + jnp.einsum("shk,hkd->sd", o, w["wo"], precision=HI)
    x = act(_rms(h, w["mlp_norm"], eps), kind)
    g = jnp.einsum("sd,df->sf", x, w["w_gate"], precision=HI)
    u = jnp.einsum("sd,df->sf", x, w["w_up"], precision=HI)
    return h + jnp.einsum("sf,fd->sd", act(jax.nn.silu(g) * u, kind),
                          w["w_down"], precision=HI)


@partial(jax.jit, static_argnames=("shape_items", "kind"))
def _layer_weights(root, layer, *, shape_items, kind):
    shape = dict(shape_items)
    out = {}
    for n in LAYER_LEAVES:
        w = block_draw(root, shape, n, layer)
        out[n] = quantize(w, CONTRACTED[n], kind) if n in CONTRACTED else w
    return out


def layer_weights(root, shape: dict, index: int, kind: str | None) -> dict:
    """Layer ``index``'s weights drawn again from the seed, matmul weights
    rounded to ``kind`` (one scale per output channel)."""
    return _layer_weights(root, index, shape_items=tuple(sorted(
        shape.items())), kind=kind)


def layer(h, w: dict, shape: dict, kind: str | None):
    """One block of the reference over one sequence ``h`` (S, D)."""
    return _layer(h, w, H=shape["num_attention_heads"],
                  K=shape["num_key_value_heads"],
                  theta=float(shape["rope_theta"]),
                  eps=float(shape["rms_norm_eps"]),
                  window=int(shape.get("sliding_window") or 0), kind=kind)


def final_norm(h, gain, shape: dict):
    return _rms(h, gain, float(shape["rms_norm_eps"]))


# ---- counts: what the algorithm needs, not what an implementation does:
# attention reads the keys and values of the positions a token attends
# to, and nothing of the pages or rows a kernel walks without needing them

def layer_params(shape: dict) -> int:
    """Matmul weights of one block."""
    D, H, K, dh, F = (shape["hidden_size"], shape["num_attention_heads"],
                      shape["num_key_value_heads"], shape["head_dim"],
                      shape["intermediate_size"])
    return 2 * D * H * dh + 2 * D * K * dh + 3 * D * F


def attention_flops(shape: dict, keys: int) -> float:
    """QK^T and PV over ``keys`` (query, key) pairs summed over tokens,
    all layers."""
    return (4.0 * keys * shape["num_attention_heads"] * shape["head_dim"]
            * shape["num_hidden_layers"])


def decode_attention_bytes(shape: dict, keys: int, tokens: int) -> float:
    """Keys and values read for ``keys`` attended positions, plus each
    decoded token's query read and output written, all layers."""
    L, H, K, dh = (shape["num_hidden_layers"], shape["num_attention_heads"],
                   shape["num_key_value_heads"], shape["head_dim"])
    return float(L * (keys * K * dh * 2 * KV_BYTES
                      + tokens * 2 * H * dh * ACT_BYTES))


def model_flops(shape: dict, tokens: int, keys: int,
                logit_rows: int) -> float:
    """Forward FLOPs of ``tokens`` tokens attending ``keys`` positions in
    all, with output logits computed for ``logit_rows`` of them."""
    return (2.0 * layer_params(shape) * shape["num_hidden_layers"] * tokens
            + attention_flops(shape, keys)
            + 2.0 * shape["hidden_size"] * shape["vocab_size"] * logit_rows)
