"""Tiled GEMM Pallas kernel — TPU-native rebuild of the paper's §4 benchmark.

The Fig. 4 row×column tiling becomes a (M/bm, N/bn, K/bk) grid with fp32
accumulation in a VMEM scratch tile; the paper's "buffered columns"
capacity knob (32 on Zynq / 128 on ZynqUS+, limited by BRAM) becomes the
``bn`` block dimension, bounded by VMEM (16 MiB) and MXU alignment (128).
benchmarks/bench_gemm.py sweeps it exactly like Table 2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def gemm(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
         bk: int = 512, interpret: bool = False) -> jax.Array:
    """C = A @ B with explicit VMEM tiling. Shapes must divide the blocks."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape,
                                                         (bm, bn, bk))
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        # M/N grid axes carry independent output tiles → megacore-parallel;
        # K is the fp32 accumulation and must stay sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


def vmem_bytes(bm: int, bn: int, bk: int, itemsize: int = 2) -> int:
    """Working-set estimate for block-shape selection (the capacity law)."""
    return (bm * bk + bk * bn) * itemsize + bm * bn * 4
