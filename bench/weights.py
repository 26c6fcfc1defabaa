"""Seeded model weights, drawn by name and layer.

Each weight of the model is drawn by itself from the run's seed, its
canonical name and its layer index, so the serving run (which draws them
all at once, stacked the way the program keeps them) and the reference
(which draws one layer at a time, long after the program's copy is gone)
get the same numbers without sharing an array. The draws use only integer
bits and exactly rounded float32 arithmetic, so they agree bit for bit
between one fused program and many small ones, and between CPU and TPU.

Values are uniform with the standard deviations the program's own
initialiser uses (0.02, and 0.02/sqrt(2L) for the projections into the
residual stream); norm gains are 1 +- 0.1, so a norm that ignored its
gain would show.

This module knows nothing of the program: it is shared by the harness,
which lays the draws into the program's parameter tree, and by the plain
reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# canonical leaf → id folded into the key (append only: ids are data)
LEAF_IDS = {"embed": 1, "unembed": 2, "final_norm": 3, "attn_norm": 4,
            "wq": 5, "wk": 6, "wv": 7, "wo": 8, "mlp_norm": 9,
            "w_gate": 10, "w_up": 11, "w_down": 12}
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")
NORMS = ("attn_norm", "mlp_norm", "final_norm")
# axes each matmul weight is contracted over (its input side)
CONTRACTED = {"embed": (1,), "unembed": (0,), "wq": (0,), "wk": (0,),
              "wv": (0,), "wo": (0, 1), "w_gate": (0,), "w_up": (0,),
              "w_down": (0,)}


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    k = jax.random.PRNGKey(0)
    while True:
        k = jax.random.fold_in(k, seed & 0x7FFFFFFF)
        seed >>= 31
        if not seed:
            return k


VOCAB_BLOCK = 8192     # the vocabulary leaves are drawn in blocks of rows


def vocab_blocks(V: int) -> list[tuple[int, int]]:
    """(first id, ids) of each vocabulary block."""
    return [(b, min(VOCAB_BLOCK, V - b)) for b in range(0, V, VOCAB_BLOCK)]


def leaf_shape(shape: dict, name: str, rows: int = 0) -> tuple[int, ...]:
    """Canonical shape of one draw: one layer's slice of a layer leaf, or
    a vocabulary block of ``rows`` ids of ``embed`` (rows) and
    ``unembed`` (columns)."""
    D, H, K, dh = (shape["hidden_size"], shape["num_attention_heads"],
                   shape["num_key_value_heads"], shape["head_dim"])
    F, V = shape["intermediate_size"], rows
    return {"embed": (V, D), "unembed": (D, V), "final_norm": (D,),
            "attn_norm": (D,), "mlp_norm": (D,), "wq": (D, H, dh),
            "wk": (D, K, dh), "wv": (D, K, dh), "wo": (H, dh, D),
            "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}[name]


def leaf_std(shape: dict, name: str) -> float:
    if name in ("wo", "w_down"):
        return 0.02 / max(1.0, (2 * shape["num_hidden_layers"]) ** 0.5)
    return 0.02


def draw(root: jax.Array, shape: dict, name: str, index, rows: int = 0,
         dtype=None) -> jax.Array:
    """One draw, float32 unless ``dtype`` is given: layer ``index`` of a
    layer leaf, vocabulary block ``index`` (of ``rows`` ids, see
    ``vocab_blocks``) of ``embed``/``unembed``, or (index 0) the final
    norm. Matmul weights are uniform with the leaf's standard deviation;
    norm gains 1 +- 0.1. ``index`` may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(root, LEAF_IDS[name]), index)
    bits = jax.random.bits(k, leaf_shape(shape, name, rows), jnp.uint32)
    u = (bits >> 8).astype(jnp.int32).astype(jnp.float32)  # exact, < 2**24
    centred = u - jnp.float32(2 ** 23 - 0.5)                 # exact
    if name in NORMS:
        x = 1.0 + centred * jnp.float32(0.1 / 2 ** 23)
    else:
        half = leaf_std(shape, name) * 3 ** 0.5              # uniform's std
        x = centred * jnp.float32(half / 2 ** 23)
        x = x.astype(jnp.bfloat16).astype(jnp.float32)       # served in bf16
    return x if dtype is None else x.astype(dtype)
