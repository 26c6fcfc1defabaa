"""The system under test, built from a configuration file.

The engine is the program's own (``repro.serve``); what this module adds
is the benchmark's side of the interface: the registered model config
checked against the file's published sizes, the seeded weights of
``bench.weights`` laid into the program's parameter tree in one jitted
call on the device, and the engine (or, for ``replicas: N``, a
``MultiEngine`` pool of one-chip tiers) with the file's settings.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.weights import draw, root_key, vocab_blocks
from repro.configs import get_config
from repro.models.model import model_defs
from repro.models.transformer import layer_schedule
from repro.serve.engine import Engine
from repro.serve.multi_engine import EngineTier, MultiEngine
from repro.sharding import params as prm
from repro.sharding.axes import single_device_ctx

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
# program leaf path inside one block -> canonical leaf of bench.weights
BLOCK_LEAVES = {("norm1",): "attn_norm", ("attn", "wq"): "wq",
                ("attn", "wk"): "wk", ("attn", "wv"): "wv",
                ("attn", "wo"): "wo", ("norm2",): "mlp_norm",
                ("mlp", "w_gate"): "w_gate", ("mlp", "w_up"): "w_up",
                ("mlp", "w_down"): "w_down"}
ENGINE_KEYS = ("max_slots", "max_len", "paged", "page_size", "num_pages",
               "prefill_batch", "min_bucket", "decode_quantum")


def model_shape(conf: dict) -> dict:
    """The published sizes (as run) that the reference and counts read."""
    return {k: conf[k] for k in FIELDS.values()}


def program_config(conf: dict):
    """The registered config with only its depth set from the file, after
    checking that every other size and setting is the file's."""
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


def _block_tree(defs, fn, path=()):
    if isinstance(defs, dict):
        return {k: _block_tree(v, fn, path + (k,)) for k, v in defs.items()}
    return fn(path, defs)


def params_fn(cfg, shape: dict):
    """``root key -> parameter tree`` in the program's layout, every leaf
    a stack or concatenation of ``bench.weights`` draws."""
    defs = model_defs(cfg)
    segments = layer_schedule(cfg)
    if set(defs) != {"embed", "blocks", "final_norm", "unembed"} or set(
            defs["embed"]) != {"table"} or set(defs["unembed"]) != {"w"}:
        raise ValueError(f"unexpected parameter tree: {sorted(defs)}")
    V = shape["vocab_size"]

    def build(root):
        blocks = [vocab_blocks(V)]
        out = {
            "embed": {"table": jnp.concatenate(
                [draw(root, shape, "embed", b, n)
                 for b, (_, n) in enumerate(blocks[0])], 0)},
            "unembed": {"w": jnp.concatenate(
                [draw(root, shape, "unembed", b, n)
                 for b, (_, n) in enumerate(blocks[0])], 1)},
            "final_norm": draw(root, shape, "final_norm", 0),
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
                        [draw(root, shape, BLOCK_LEAVES[path],
                              base + r * plen + j) for r in range(seg.repeat)])
                slot[f"s{j}"] = _block_tree(seg_defs[f"s{j}"], leaf)
            out["blocks"].append(slot)
            base += plen * seg.repeat

        def cast(x, d):
            if x.shape != d.shape:
                raise ValueError(f"shape {x.shape} != program's {d.shape}")
            return x.astype(d.dtype)
        return jax.tree.map(cast, out, defs, is_leaf=prm.is_def)
    return build, defs


def build_server(conf: dict, seed: int, devices):
    """Engine (or replica pool) over the seeded weights on ``devices``."""
    cfg = program_config(conf)
    shape = model_shape(conf)
    kw = {k: conf["engine"][k] for k in ENGINE_KEYS if k in conf["engine"]}
    build, defs = params_fn(cfg, shape)
    replicas = int(conf.get("replicas", 1))
    ctxs = [single_device_ctx(d) for d in devices[:replicas]]
    root = root_key(seed)
    params = jax.jit(build, out_shardings=prm.shardings(defs, ctxs[0]))(root)
    if replicas == 1:
        return Engine(cfg, params, ctxs[0], **kw)
    tiers = []
    for i, ctx in enumerate(ctxs):
        p = params if i == 0 else jax.device_put(params,
                                                 prm.shardings(defs, ctx))
        tiers.append(EngineTier(f"chip{i}", Engine(cfg, p, ctx, **kw)))
    return MultiEngine(tiers)


def engines(server) -> list[Engine]:
    return ([t.engine for t in server.tiers] if isinstance(server, MultiEngine)
            else [server])
