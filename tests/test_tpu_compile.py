"""Compile the paged-attention decode kernels for a TPU v5e that is
described, not attached, at published widths.

Interpret mode runs the kernel body on the CPU but never applies the
chip's tiling and memory rules; the TPU compiler here does. Each case
lowers the kernel exactly as the serving engine calls it (bf16 query and
pool, 8 slots, page size 16, a 128-page live table) and asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.paged_attention import (
    paged_flash_decode_gqa, paged_flash_decode_mla)

B, PAGE, T = 8, 16, 128            # slots, page size, live table width
N_PAGES = 1 + B * T                # pool incl. the reserved trash page 0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on the described chip, with the persistent compile cache
    off: an entry written for a chip that is not attached cannot be read
    back, and the next compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table_args(sh):
    return (_spec((B, T), jnp.int32, sh), _spec((B,), jnp.int32, sh),
            _spec((), jnp.int32, sh))


# (Hkv, G, dh): Mistral-NeMo-12B (32 q / 8 kv heads, head_dim 128) and
# H2O-Danube-1.8B (32 q / 8 kv heads, head_dim 80 — not a lane multiple)
@pytest.mark.parametrize("hkv,grp,dh", [(8, 4, 128), (8, 4, 80)],
                         ids=["nemo-dh128", "danube-dh80"])
def test_paged_gqa_kernel_compiles_for_v5e(one_chip, hkv, grp, dh):
    q = _spec((B, hkv, grp, dh), jnp.bfloat16, one_chip)
    pool = _spec((N_PAGES, PAGE, hkv, dh), jnp.bfloat16, one_chip)
    compiled = paged_flash_decode_gqa.lower(
        q, pool, pool, *_table_args(one_chip), page_size=PAGE,
        scale=dh ** -0.5).compile()
    assert "tpu_custom_call" in compiled.as_text()
    o, m, l = compiled.out_info
    assert o.shape == (B, hkv * grp, dh) and m.shape == l.shape == (
        B, hkv * grp)


def test_paged_mla_kernel_compiles_for_v5e(one_chip):
    """DeepSeek-V2 absorbed MLA: 128 heads, latent row 512 + 64 rope."""
    H, kv_lora, R = 128, 512, 576
    q = _spec((B, H, R), jnp.bfloat16, one_chip)
    pool = _spec((N_PAGES, PAGE, R), jnp.bfloat16, one_chip)
    compiled = paged_flash_decode_mla.lower(
        q, pool, *_table_args(one_chip), page_size=PAGE, kv_lora=kv_lora,
        scale=192 ** -0.5).compile()
    assert "tpu_custom_call" in compiled.as_text()
    o, m, l = compiled.out_info
    assert o.shape == (B, H, kv_lora) and m.shape == l.shape == (B, H)
