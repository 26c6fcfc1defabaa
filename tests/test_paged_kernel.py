"""Pallas paged-attention decode kernel: kernel ↔ ref ↔ jnp-gather
equivalence (page sizes 8/16, multi-page slots, GQA + MLA + hybrid,
sharded and unsharded meshes), trash-page-0 isolation, and the engine's
live-prefix page-table bucketing. Kernel runs in interpret mode on CPU so
the real kernel body is exercised in tier-1."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs, smoke_config
from repro.kernels.paged_attention import ops, ref
from repro.serve.decode import flash_decode_gqa, flash_decode_mla
from repro.serve.engine import Request, make_engine

KERNEL = "interpret"          # exercise the Pallas body even on CPU


def _cfg(arch="mistral-nemo-12b"):
    return smoke_config(all_configs()[arch])


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).tolist() for n in lens]


def _gqa_case(page_size, seed=0, hkv=2, grp=3, dh=16, B=3, T=4):
    """Random pool + disjoint-page table + multi-page positions."""
    rng = np.random.default_rng(seed)
    N = 1 + B * T
    q = jnp.asarray(rng.normal(size=(B, hkv, grp, dh)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(N, page_size, hkv, dh)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(N, page_size, hkv, dh)), jnp.float32)
    pt = jnp.asarray(1 + rng.permutation(N - 1)[:B * T].reshape(B, T),
                     jnp.int32)
    # rows span 1..T live pages, incl. a page-boundary-straddling pos
    pos = jnp.asarray([page_size - 1, 2 * page_size, T * page_size - 1][:B],
                      jnp.int32)
    return q, pk, pv, pt, pos


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("softcap", [0.0, 5.0])
def test_kernel_matches_ref_gqa(page_size, softcap):
    from repro.kernels.paged_attention.paged_attention import \
        paged_flash_decode_gqa
    q, pk, pv, pt, pos = _gqa_case(page_size)
    # base = shard · ps_loc: 0 is the unsharded case, page_size//2 the
    # second shard of a 2-way model axis (kernel must offset gpos)
    for base in (0, page_size // 2):
        got = paged_flash_decode_gqa(
            q, pk, pv, pt, pos, base, page_size=page_size, scale=0.25,
            softcap=softcap, interpret=True)
        want = ref.paged_flash_decode_gqa_ref(
            q, pk, pv, pt, pos, base, page_size=page_size, scale=0.25,
            softcap=softcap)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("page_size", [8, 16])
def test_kernel_matches_ref_mla(page_size):
    rng = np.random.default_rng(1)
    B, H, R, lora, T = 3, 4, 24, 16, 4
    N = 1 + B * T
    q = jnp.asarray(rng.normal(size=(B, H, R)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(N, page_size, R)), jnp.float32)
    pt = jnp.asarray(1 + rng.permutation(N - 1)[:B * T].reshape(B, T),
                     jnp.int32)
    pos = jnp.asarray([0, page_size + 2, T * page_size - 1], jnp.int32)
    got = ops.paged_attend_mla(q, pool, pt, pos, 0, 1, kv_lora=lora,
                               scale=0.2, impl=KERNEL)
    want = ref.paged_flash_decode_mla_ref(q, pool, pt, pos, 0,
                                          page_size=page_size, kv_lora=lora,
                                          scale=0.2)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------- full decode path, both flags
def test_flash_decode_gqa_kernel_vs_gather(ctx):
    """kernel and jnp-gather paths agree through the full flash_decode_gqa
    contract (write of the new token included)."""
    q, pk, pv, pt, pos = _gqa_case(8, seed=4)
    kn = jnp.asarray(np.random.default_rng(5).normal(size=(3, 2, 16)),
                     jnp.float32)
    vn = jnp.asarray(np.random.default_rng(6).normal(size=(3, 2, 16)),
                     jnp.float32)
    outs = {}
    for flag in (KERNEL, False):
        o, ck, cv = flash_decode_gqa(q, kn, vn, pk, pv, pos, window=0,
                                     scale=0.25, softcap=0.0, ctx=ctx,
                                     page_table=pt, paged_kernel=flag)
        outs[flag] = (np.asarray(o), np.asarray(ck), np.asarray(cv))
    for a, b in zip(outs[KERNEL], outs[False]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_flash_decode_mla_kernel_vs_gather(ctx):
    rng = np.random.default_rng(7)
    B, H, R, lora, T, ps = 2, 4, 24, 16, 3, 8
    N = 1 + B * T
    q = jnp.asarray(rng.normal(size=(B, H, R)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(N, ps, R)), jnp.float32)
    row = jnp.asarray(rng.normal(size=(B, R)), jnp.float32)
    pt = jnp.asarray(1 + rng.permutation(N - 1)[:B * T].reshape(B, T),
                     jnp.int32)
    pos = jnp.asarray([5, 2 * ps + 3], jnp.int32)
    outs = {}
    for flag in (KERNEL, False):
        o, ckv = flash_decode_mla(q, row, pool, pos, kv_lora=lora, scale=0.2,
                                  ctx=ctx, page_table=pt, paged_kernel=flag)
        outs[flag] = (np.asarray(o), np.asarray(ckv))
    for a, b in zip(outs[KERNEL], outs[False]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_trash_page_isolation(ctx):
    """Garbage in the reserved trash page 0 (scribbles of inactive slots)
    must never reach a live slot's output — dead table entries point at
    page 0 and are position-masked/block-skipped."""
    q, pk, pv, pt, pos = _gqa_case(8, seed=8)
    kn = jnp.zeros((3, 2, 16), jnp.float32)
    vn = jnp.zeros((3, 2, 16), jnp.float32)
    # row 0 uses only 1 of its 4 table entries; point the dead ones at 0
    pt = pt.at[0, 1:].set(0)
    pos = pos.at[0].set(3)
    garbage = pk.at[0].set(1e4).astype(jnp.float32)
    for flag in (KERNEL, False):
        o_clean, _, _ = flash_decode_gqa(q, kn, vn, pk, pv, pos, window=0,
                                         scale=0.25, softcap=0.0, ctx=ctx,
                                         page_table=pt, paged_kernel=flag)
        o_trash, _, _ = flash_decode_gqa(q, kn, vn, garbage,
                                         pv.at[0].set(-1e4), pos, window=0,
                                         scale=0.25, softcap=0.0, ctx=ctx,
                                         page_table=pt, paged_kernel=flag)
        np.testing.assert_allclose(np.asarray(o_clean)[0],
                                   np.asarray(o_trash)[0],
                                   rtol=1e-6, atol=1e-6)


def test_paged_kernel_rejects_bad_args(ctx):
    """Typed errors (not asserts) on the user-reachable paged branches."""
    q, pk, pv, pt, pos = _gqa_case(8)
    kn = jnp.zeros((3, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="full-attention"):
        flash_decode_gqa(q, kn, kn, pk, pv, pos, window=16, scale=0.25,
                         softcap=0.0, ctx=ctx, page_table=pt)
    with pytest.raises(ValueError, match="update"):
        flash_decode_gqa(q, kn, kn, pk, pv, pos, window=0, scale=0.25,
                         softcap=0.0, ctx=ctx, page_table=pt, update=False)
    with pytest.raises(ValueError, match="batch"):
        flash_decode_gqa(q, kn, kn, pk, pv, pos[:2], window=0, scale=0.25,
                         softcap=0.0, ctx=ctx, page_table=pt)
    with pytest.raises(ValueError, match="impl"):
        ops.paged_attend_gqa(q, pk, pv, pt, pos, 0, 1, scale=0.25,
                             impl="nope")
    with pytest.raises(ValueError, match="paged_kernel"):  # at construction,
        make_engine(_cfg(), ctx, paged=True, page_size=8,  # not mid-serve
                    paged_kernel="kernal")


# ----------------------------------------------------- engine, end to end
def _serve(cfg, ctx, prompts, max_new, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("decode_quantum", 4)
    eng = make_engine(cfg, ctx, **kw)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return eng, reqs


@pytest.mark.parametrize("arch,page_size",
                         [("mistral-nemo-12b", 8),
                          ("deepseek-v2-236b", 16),
                          ("jamba-v0.1-52b", 8)])
def test_engine_kernel_matches_gather(arch, page_size, ctx):
    """GQA + MLA + hybrid: interpret-mode kernel decode is token-identical
    to the PR 2 jnp-gather escape hatch (multi-page contexts included)."""
    cfg = _cfg(arch)
    prompts = _prompts(cfg, [5, 11, 19], seed=2)
    kw = dict(paged=True, page_size=page_size)
    if arch == "jamba-v0.1-52b":
        kw["max_len"] = 48
    _, kern = _serve(cfg, ctx, prompts, 6, paged_kernel=KERNEL, **kw)
    _, gath = _serve(cfg, ctx, prompts, 6, paged_kernel=False, **kw)
    for a, b in zip(kern, gath):
        assert a.done and a.out == b.out, (a.rid, a.out, b.out)


def test_engine_live_prefix_buckets(ctx):
    """The kernel path hands the decode loop only the live page-column
    prefix: a short-context quantum must see a narrower table than
    max_len/page_size, bucketed to a power of two."""
    cfg = _cfg()
    eng = make_engine(cfg, ctx, max_slots=2, max_len=256, decode_quantum=4,
                      paged=True, page_size=8)
    assert eng.pages_per_slot == 32
    eng.pos_host[0] = 5                    # short ctx → the 8-page floor
    assert eng._live_page_table([0]).shape == (2, 8)
    eng.pos_host[1] = 100                  # 104 → 13 pages → bucket 16
    assert eng._live_page_table([0, 1]).shape == (2, 16)
    eng.pos_host[1] = 255                  # capped at the full table
    assert eng._live_page_table([0, 1]).shape == (2, 32)
    # gather escape hatch always sees the full table
    eng2 = make_engine(cfg, ctx, max_slots=2, max_len=256, decode_quantum=4,
                       paged=True, page_size=8, paged_kernel=False)
    eng2.pos_host[0] = 5
    assert eng2._live_page_table([0]).shape == (2, 32)


# 4-way model-sharded mesh: in-kernel base offsets + cross-shard combine
# (8-device subprocess, matching the test_paged.py convention)
_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.configs import all_configs, smoke_config
    from repro.serve.decode import flash_decode_gqa
    from repro.serve.engine import Request, make_engine
    from repro.sharding.axes import ShardCtx, make_mesh

    mesh = make_mesh((1, 4), ("data", "model"))
    ctx = ShardCtx(mesh=mesh)

    # direct kernel-vs-gather on the sharded pool (interpret kernel): each
    # shard owns 2 of the 8 in-page offsets → exercises base = i·ps_loc
    rng = np.random.default_rng(0)
    B, hkv, grp, dh, T, ps = 2, 2, 2, 16, 3, 8
    N = 1 + B * T
    q = jnp.asarray(rng.normal(size=(B, hkv, grp, dh)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(N, ps, hkv, dh)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(N, ps, hkv, dh)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, hkv, dh)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, hkv, dh)), jnp.float32)
    pt = jnp.asarray(1 + rng.permutation(N - 1)[:B * T].reshape(B, T),
                     jnp.int32)
    pos = jnp.asarray([5, 2 * ps + 3], jnp.int32)
    outs = {}
    for flag in ("interpret", False):
        o, ck, cv = flash_decode_gqa(q, kn, vn, pk, pv, pos, window=0,
                                     scale=0.25, softcap=0.0, ctx=ctx,
                                     page_table=pt, paged_kernel=flag)
        outs[flag] = (np.asarray(o), np.asarray(ck), np.asarray(cv))
    for a, b in zip(outs["interpret"], outs[False]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    print("KERNEL-SHARD-DIRECT-OK")

    # engine end-to-end on the same mesh: the interpret-mode Pallas kernel
    # (pinned — "auto" would resolve to the ref path on the CPU host and
    # make this a tautology) vs the gather escape hatch, token-identical
    cfg = smoke_config(all_configs()["mistral-nemo-12b"])
    prompts = [np.random.default_rng(2).integers(0, cfg.vocab, n).tolist()
               for n in (5, 11, 19)]

    def serve(**kw):
        eng = make_engine(cfg, ctx, max_slots=2, max_len=64,
                          decode_quantum=4, paged=True, page_size=8, **kw)
        reqs = [Request(rid=i, prompt=p, max_new=12)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        return reqs

    kern = serve(paged_kernel="interpret")
    gath = serve(paged_kernel=False)
    for a, b in zip(kern, gath):
        assert a.done and a.out == b.out, (a.rid, a.out, b.out)
    print("KERNEL-SHARD-ENGINE-OK")
""")


@pytest.mark.slow
def test_paged_kernel_model_sharded():
    r = subprocess.run([sys.executable, "-c", _SHARDED],
                       env=dict(os.environ, PYTHONPATH="src"),
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "KERNEL-SHARD-DIRECT-OK" in r.stdout, (r.stdout[-2000:]
                                                  + r.stderr[-2000:])
    assert "KERNEL-SHARD-ENGINE-OK" in r.stdout, (r.stdout[-2000:]
                                                  + r.stderr[-2000:])
