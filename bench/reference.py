"""Plain float32 reference of the served models, and the comparison that
decides ``correct``.

The forward pass is the configuration's model module's
(``bench/models``: for ``plain``, the published Llama/Mistral block)
between what every model here shares: the embedding, the final norm's
gain and an untied output projection, drawn in vocabulary blocks. Shapes
come from the configuration file's published keys. Everything is float32
at ``HIGHEST`` matmul precision, one layer at a time, one request at a
time, and the weights are drawn again from the seed (``bench.weights``):
nothing here imports or receives anything the program made.

What is compared (``logit_gaps``): the reference runs over each sampled
request's prompt followed by the tokens the program served, and at each
served position reads how far the served token's logit lies below the
reference's best. A greedy server that computes what the configuration
states only picks a token other than the reference's best where two
logits lie closer than its rounding, so the widest such gap stays small;
a wrong cache entry, position, mask or token shows as a gap of the order
of the logits' spread.

The control (``controls=``) is this same reference computed in a lower
precision (float8 e4m3 or int8, symmetric absmax scales): every matmul
weight rounded with one scale per output channel, and every matmul input,
key and value with one scale per token (and head). At the same positions
it reads the gap of the token that the lower precision puts first.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import CONTRACTED, root_key, shared_draw, vocab_blocks

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 512          # query rows per attention block
QMAX = {"fp8": 448.0, "int8": 127.0}


def quantize(x: jax.Array, axes, kind: str | None) -> jax.Array:
    """Round x to ``kind`` with one absmax scale per slice over ``axes``
    (a weight's input side: one scale per output channel), and return it
    dequantized to float32. ``kind`` None leaves x as it is."""
    if kind is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / QMAX[kind]
    y = x / scale
    if kind == "fp8":
        y = y.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        y = jnp.clip(jnp.round(y), -127, 127)
    return y * scale


def act(x: jax.Array, kind: str | None) -> jax.Array:
    """An activation rounded to ``kind``, one scale per token (and head)."""
    return quantize(x, -1, kind)


@partial(jax.jit, static_argnames=("shape_items", "name", "rows", "kind"))
def _leaf(root, index, *, shape_items, name, rows, kind):
    """A shared leaf's draw, rounded to ``kind`` where it is a matmul
    weight."""
    w = shared_draw(root, dict(shape_items), name, index, rows)
    return quantize(w, CONTRACTED[name], kind) if name in CONTRACTED else w


@jax.jit
def _embed_block(h, table, toks, v0):
    """Add the rows of ``toks`` that fall in the block starting at id v0."""
    idx = toks - v0
    ok = (idx >= 0) & (idx < table.shape[0])
    rows = jnp.take(table, jnp.clip(idx, 0, table.shape[0] - 1), axis=0)
    return h + jnp.where(ok[:, None], rows, 0.0)


@partial(jax.jit, static_argnames=("model", "shape_items"))
def _final_rows(h, rows, gain, *, model, shape_items):
    return model.final_norm(jnp.take(h, rows, axis=0), gain,
                            dict(shape_items))


@jax.jit
def _logit_block(h_ref, u_ref, served, h_ctls, u_ctls, carry):
    """Fold one vocabulary block into the running statistics. ``served``
    is relative to the block's first id.

    carry: (ref max, served token's ref logit, per control: its own max,
    and the ref logit of the token it puts first)."""
    ref_max, ref_served, ctl_max, ctl_pick = carry
    lg = jnp.einsum("nd,dv->nv", h_ref, u_ref, precision=HI)
    ref_max = jnp.maximum(ref_max, lg.max(-1))
    hit = served[:, None] == jnp.arange(lg.shape[1])[None]
    ref_served = ref_served + jnp.sum(jnp.where(hit, lg, 0.0), -1)
    new_max, new_pick = [], []
    for h_c, u_c, m, p in zip(h_ctls, u_ctls, ctl_max, ctl_pick):
        lc = jnp.einsum("nd,dv->nv", h_c, u_c, precision=HI)
        a = jnp.argmax(lc, -1)
        top = jnp.take_along_axis(lc, a[:, None], -1)[:, 0]
        ref_at = jnp.take_along_axis(lg, a[:, None], -1)[:, 0]
        better = top > m
        new_max.append(jnp.where(better, top, m))
        new_pick.append(jnp.where(better, ref_at, p))
    return ref_max, ref_served, tuple(new_max), tuple(new_pick)


def _bucket(n: int) -> int:
    """Padded length: a power of two of at least one query block, so a
    run compiles the layer for a few lengths only."""
    b = Q_CHUNK
    while b < n:
        b *= 2
    return b


def logit_gaps(model, shape: dict, seed: int, samples, *,
               controls=()) -> dict:
    """Widest logit gap of the served tokens, and of each control, with
    the block of ``model`` (a module of ``bench/models``) over ``shape``.

    samples: ``(prompt, served)`` token lists. Returns ``{"program":
    widest gap, "controls": {kind: widest gap}, "positions": count}``,
    with each one's 99th percentile gap and share of positions whose
    token is not the reference's best beside them.
    """
    root = root_key(seed)
    items = tuple(sorted(shape.items()))
    kinds = (None,) + tuple(controls)
    seqs, rows, served = [], [], []
    for prompt, out in samples:
        toks = list(prompt) + list(out[:-1])
        pad = _bucket(len(toks))
        seqs.append(np.pad(np.asarray(toks, np.int32), (0, pad - len(toks))))
        rows.append(np.arange(len(prompt) - 1, len(toks), dtype=np.int32))
        served.append(np.asarray(out, np.int32))
    D, V = shape["hidden_size"], shape["vocab_size"]
    blocks = vocab_blocks(V)
    hs = {kind: [jnp.zeros((len(t), D), jnp.float32) for t in seqs]
          for kind in kinds}
    for b, (v0, nv) in enumerate(blocks):
        for kind in kinds:
            table = _leaf(root, b, shape_items=items, name="embed", rows=nv,
                          kind=kind)
            hs[kind] = [_embed_block(h, table, jnp.asarray(t), v0)
                        for h, t in zip(hs[kind], seqs)]
            del table
    for layer in range(shape["num_hidden_layers"]):
        for kind in kinds:
            w = model.layer_weights(root, shape, layer, kind)
            hs[kind] = [model.layer(h, w, shape, kind) for h in hs[kind]]
            del w
    gain = _leaf(root, 0, shape_items=items, name="final_norm", rows=0,
                 kind=None)
    fin = {kind: act(jnp.concatenate(
        [_final_rows(h, jnp.asarray(r), gain, model=model, shape_items=items)
         for h, r in zip(hs[kind], rows)]), kind) for kind in kinds}
    del hs
    served_all = jnp.asarray(np.concatenate(served))
    n = int(served_all.shape[0])
    neg = jnp.full((n,), -jnp.inf, jnp.float32)
    carry = (neg, jnp.zeros((n,), jnp.float32),
             tuple(neg for _ in controls), tuple(neg for _ in controls))
    for b, (v0, nv) in enumerate(blocks):
        u = {kind: _leaf(root, b, shape_items=items, name="unembed", rows=nv,
                         kind=kind) for kind in kinds}
        carry = _logit_block(fin[None], u[None], served_all - v0,
                             tuple(fin[k] for k in controls),
                             tuple(u[k] for k in controls), carry)
    ref_max, ref_served, _, ctl_pick = carry
    gaps = {"program": np.asarray(ref_max - ref_served)}
    gaps.update((k, np.asarray(ref_max - p)) for k, p in zip(controls,
                                                              ctl_pick))
    return {"program": float(gaps["program"].max()),
            "controls": {k: float(gaps[k].max()) for k in controls},
            "positions": n,
            # not compared: how the gaps spread, for setting the limits
            "p99": {k: float(np.percentile(g, 99)) for k, g in gaps.items()},
            "flipped": {k: float(np.mean(g > 0)) for k, g in gaps.items()}}
