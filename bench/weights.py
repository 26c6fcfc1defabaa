"""Seeded model weights, drawn by name and layer.

Each weight of the model is drawn by itself from the run's seed, its
leaf's id and its layer index, so the serving run (which draws them all
at once, stacked the way the program keeps them) and the reference
(which draws one layer at a time, long after the program's copy is gone)
get the same numbers without sharing an array. The draws use only integer
bits and exactly rounded float32 arithmetic, so they agree bit for bit
between one fused program and many small ones, and between CPU and TPU.

Values are uniform with a given standard deviation (the program's own
initialiser uses 0.02, and 0.02/sqrt(2L) for the projections into the
residual stream); norm gains are 1 +- 0.1, so a norm that ignored its
gain would show.

This module holds the draw itself and the leaves every model shares (the
embedding, the final norm's gain and the output head); a model module
(``bench/models``) gives the shapes and ids of its blocks' leaves. It
knows nothing of the program: it is shared by the harness, which lays the
draws into the program's parameter tree, and by the plain reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# shared leaf -> id folded into the key. Ids are data: append only; a
# model module's own leaves take ids of their own (4-12: ``plain``)
LEAF_IDS = {"embed": 1, "unembed": 2, "final_norm": 3}
# axes each shared matmul weight is contracted over (its input side)
CONTRACTED = {"embed": (1,), "unembed": (0,)}
EMBED_STD = 0.02


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


def draw(root: jax.Array, leaf_id: int, index, dims: tuple[int, ...],
         std: float | None, dtype=None) -> jax.Array:
    """One draw of shape ``dims``, float32 unless ``dtype`` is given:
    uniform with standard deviation ``std`` and rounded to bfloat16 as
    served, or for ``std=None`` a norm gain, 1 +- 0.1. ``index`` (a
    layer, a vocabulary block) may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(root, leaf_id), index)
    bits = jax.random.bits(k, dims, jnp.uint32)
    u = (bits >> 8).astype(jnp.int32).astype(jnp.float32)  # exact, < 2**24
    centred = u - jnp.float32(2 ** 23 - 0.5)                 # exact
    if std is None:
        x = 1.0 + centred * jnp.float32(0.1 / 2 ** 23)
    else:
        half = std * 3 ** 0.5                                # uniform's std
        x = centred * jnp.float32(half / 2 ** 23)
        x = x.astype(jnp.bfloat16).astype(jnp.float32)       # served in bf16
    return x if dtype is None else x.astype(dtype)


def shared_draw(root: jax.Array, shape: dict, name: str, index: int = 0,
                rows: int = 0, dtype=None) -> jax.Array:
    """A draw of a shared leaf: vocabulary block ``index`` (of ``rows``
    ids, see ``vocab_blocks``) of ``embed`` (rows) or ``unembed``
    (columns), or the ``final_norm`` gain (index 0)."""
    D = shape["hidden_size"]
    dims, std = {"embed": ((rows, D), EMBED_STD),
                 "unembed": ((D, rows), EMBED_STD),
                 "final_norm": ((D,), None)}[name]
    return draw(root, LEAF_IDS[name], index, dims, std, dtype)
