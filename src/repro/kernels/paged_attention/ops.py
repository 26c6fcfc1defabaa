"""Public paged-attention decode ops with backend dispatch.

Called per model shard from inside the decode `shard_map`
(`serve/decode.py`): inputs are the shard-local page pools — the whole
layer stack `(L, N, ps_loc, …)` with the traced layer index, as the decode
layer scan carries it, or one layer's `(N, ps_loc, …)` pool read as a
stack of one — and the traced shard index; outputs the unnormalized
(o, m, l) softmax partials the caller feeds to the cross-shard exact
`_combine`.

Dispatch (``impl`` arg):
  "auto"      TPU → compiled Pallas kernel; other backends → "ref". The
              Pallas interpreter is an emulator (~50× the fused-XLA cost),
              so it is never a default *serving* path off-TPU.
  "kernel"    the Pallas kernel, interpret mode off-TPU.
  "interpret" the Pallas kernel, interpret mode everywhere — what the
              tier-1 tests pin so the real kernel body is exercised on
              CPU on every run (tests/test_paged_kernel.py).
  "ref"       the jnp oracle in ``ref.py`` — same blockwise contract
              (shard-local partials over the live table prefix), fused by
              XLA. Off-TPU serving default.
"""
from __future__ import annotations

import jax

from repro.kernels.paged_attention import ref
from repro.kernels.paged_attention.paged_attention import (
    paged_flash_decode_gqa, paged_flash_decode_mla)

_IMPLS = ("auto", "kernel", "interpret", "ref")


def _resolve(impl: str) -> tuple[str, bool]:
    """→ (path, interpret) where path ∈ {"kernel", "ref"}; "" is "auto"."""
    impl = impl or "auto"
    if impl not in _IMPLS:
        raise ValueError(f"paged-attention impl {impl!r}: expected one of "
                         f"{_IMPLS}")
    tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = "kernel" if tpu else "ref"
    if impl == "ref":
        return "ref", False
    return "kernel", impl == "interpret" or not tpu


def paged_attend_gqa(q, pool_k, pool_v, page_table, pos, shard, msize,
                     layer=0, *, scale: float, softcap: float = 0.0,
                     impl: str = ""):
    """q (B,Hkv,G,dh); pools (L, N, ps_loc, Hkv, dh) read at `layer`, or
    (N, ps_loc, Hkv, dh); page_table (B,T); pos (B,); shard = traced
    model-axis index; msize its static size.
    → (o (B,Hkv·G,dh), m (B,Hkv·G), l (B,Hkv·G)) f32 partials."""
    ps_loc = pool_k.shape[-3]
    page_size = ps_loc * msize
    base = shard * ps_loc
    path, interpret = _resolve(impl)
    if path == "ref":
        return ref.paged_flash_decode_gqa_ref(
            q, pool_k, pool_v, page_table, pos, base, layer,
            page_size=page_size, scale=scale, softcap=softcap)
    return paged_flash_decode_gqa(
        q, pool_k, pool_v, page_table, pos, base, layer, page_size=page_size,
        scale=scale, softcap=softcap, interpret=interpret)


def paged_attend_mla(q, pool, page_table, pos, shard, msize, layer=0, *,
                     kv_lora: int, scale: float, impl: str = ""):
    """q (B,H,R); pool (L, N, ps_loc, R) read at `layer`, or
    (N, ps_loc, R) → (o (B,H,kv_lora), m, l) partials."""
    ps_loc = pool.shape[-2]
    page_size = ps_loc * msize
    base = shard * ps_loc
    path, interpret = _resolve(impl)
    if path == "ref":
        return ref.paged_flash_decode_mla_ref(
            q, pool, page_table, pos, base, layer, page_size=page_size,
            kv_lora=kv_lora, scale=scale)
    return paged_flash_decode_mla(
        q, pool, page_table, pos, base, layer, page_size=page_size,
        kv_lora=kv_lora, scale=scale, interpret=interpret)
