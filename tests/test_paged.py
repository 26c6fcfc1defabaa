"""Paged KV cache: allocator invariants, paged ↔ dense ↔ legacy token
equivalence (single-device and model-sharded pools), pool-exhaustion
admission backpressure, page reuse, and the fast path's
one-blocking-fetch-per-quantum contract."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import all_configs, smoke_config
from repro.serve import engine as engine_mod
from repro.serve.engine import (EngineStallError, PageAllocator,
                                PromptTooLongError, Request, make_engine)
from repro.serve.prefill import bucket_len


def _cfg(arch="mistral-nemo-12b"):
    return smoke_config(all_configs()[arch])


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).tolist() for n in lens]


def _serve(cfg, ctx, prompts, max_new, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("decode_quantum", 4)
    eng = make_engine(cfg, ctx, **kw)
    reqs = [Request(rid=i, prompt=p,
                    max_new=max_new[i] if isinstance(max_new, list)
                    else max_new)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return eng, reqs


# ------------------------------------------------------------- allocator
def test_allocator_free_list_and_trash_page():
    al = PageAllocator(num_pages=9, max_slots=2, pages_per_slot=8)
    assert al.usable_pages == 8
    al.commit(0, 5)
    al.grow_to(0, 2)
    assert al.count[0] == 2 and 0 not in al.table[0, :2]   # page 0 reserved
    assert al.outstanding() == 3
    assert al.can_commit(3) and not al.can_commit(4)
    with pytest.raises(RuntimeError):
        al.grow_to(0, 6)                    # beyond the committed budget
    with pytest.raises(RuntimeError):
        al.commit(0, 1)                     # slot already holds pages
    al.release(0)
    assert (al.table[0] == 0).all() and len(al.free) == 8
    assert al.can_commit(8)


def test_allocator_rejects_undersized_pool():
    with pytest.raises(ValueError):
        PageAllocator(num_pages=4, max_slots=1, pages_per_slot=4)


def test_engine_paged_config_validation(ctx):
    cfg = _cfg()
    with pytest.raises(ValueError):
        make_engine(cfg, ctx, paged=True, fast=False)
    with pytest.raises(ValueError):
        make_engine(cfg, ctx, max_len=64, paged=True, page_size=13)


# ------------------------------------------------- paged ↔ dense ↔ legacy
def test_paged_matches_fast_and_legacy(ctx):
    """Same workload through paged, dense-fast and legacy engines yields
    identical token streams, and every pool page is recycled at the end."""
    cfg = _cfg()
    prompts = _prompts(cfg, [4, 5, 9, 17, 18, 23, 60])
    max_new = [6, 1, 6, 6, 6, 6, 6]         # rid 1 finishes at prefill
    engp, paged = _serve(cfg, ctx, prompts, max_new, paged=True, page_size=8)
    _, fast = _serve(cfg, ctx, prompts, max_new)
    _, legacy = _serve(cfg, ctx, prompts, max_new, fast=False)
    for a, b, c in zip(paged, fast, legacy):
        assert a.done and a.out == b.out == c.out, (a.rid, a.out, c.out)
    assert len(engp.alloc.free) == engp.alloc.usable_pages
    assert (engp.alloc.table == 0).all() and engp.alloc.outstanding() == 0


def test_paged_mla_matches_legacy(ctx):
    """MLA pools (compressed-latent pages) decode token-identically."""
    cfg = _cfg("deepseek-v2-236b")
    prompts = _prompts(cfg, [5, 11, 19], seed=1)
    _, paged = _serve(cfg, ctx, prompts, 8, max_slots=2, paged=True,
                      page_size=8)
    _, legacy = _serve(cfg, ctx, prompts, 8, max_slots=2, fast=False)
    for a, b in zip(paged, legacy):
        assert a.done and a.out == b.out, (a.rid, a.out, b.out)


def test_paged_hybrid_rings_and_state_stay_dense(ctx):
    """Hybrid (jamba): mamba state and any ring layers keep dense layouts
    while attention layers page — streams still match the reference."""
    cfg = _cfg("jamba-v0.1-52b")
    prompts = _prompts(cfg, [5, 9], seed=1)
    engp, paged = _serve(cfg, ctx, prompts, 5, max_slots=2, max_len=48,
                         paged=True, page_size=8)
    assert engp.pad_safe is False           # exact-length prefill path
    _, legacy = _serve(cfg, ctx, prompts, 5, max_slots=2, max_len=48,
                       fast=False)
    for a, b in zip(paged, legacy):
        assert a.done and a.out == b.out, (a.rid, a.out, b.out)


def test_paged_long_decode_crosses_page_boundaries(ctx):
    """A short prompt decoding far past several page boundaries must lazily
    grow its page run and stay token-identical to the legacy engine."""
    cfg = _cfg()
    prompts = _prompts(cfg, [5], seed=7)
    engp, paged = _serve(cfg, ctx, prompts, 40, paged=True, page_size=8)
    _, legacy = _serve(cfg, ctx, prompts, 40, fast=False)
    assert paged[0].done and paged[0].out == legacy[0].out
    # context reached pos ≈ 5 + 40 → at least 5 eight-token pages were live
    peak_pages = engp.alloc.usable_pages - engp.alloc.min_free
    assert peak_pages >= 5, peak_pages
    assert len(engp.alloc.free) == engp.alloc.usable_pages


def test_paged_pool_exhaustion_backpressure(ctx):
    """A pool that fits one worst-case request forces serialized admission
    (backpressure, not a crash), recycles pages between requests, and still
    completes every stream identically to the legacy engine."""
    cfg = _cfg()
    prompts = _prompts(cfg, [5, 7, 9, 11], seed=5)
    # W(req) = ceil(min(5+60-1+4, 64)/16) = 4 pages = the whole usable pool
    engp = make_engine(cfg, ctx, max_slots=3, max_len=64, decode_quantum=4,
                       paged=True, page_size=16, num_pages=5)
    paged = [Request(rid=i, prompt=p, max_new=60)
             for i, p in enumerate(prompts)]
    for r in paged:
        engp.submit(r)
    admitted = []
    while engp.has_work():
        admitted.append(engp.step().admitted)
    _, legacy = _serve(cfg, ctx, prompts, 60, fast=False)
    for a, b in zip(paged, legacy):
        assert a.done and a.out == b.out, (a.rid, a.out, b.out)
    # never more than one request's pages live at once …
    assert engp.alloc.min_free >= 0
    assert sum(admitted) == len(prompts) and max(admitted) <= 1
    # … so the four requests reused the same pages (page reuse evidence)
    assert engp.alloc.total_grants > engp.alloc.usable_pages
    assert len(engp.alloc.free) == engp.alloc.usable_pages


# model-sharded pool: exercises the msize>1 masked in-page-offset writes
# and the gpos page interleaving in _paged_write/ref._gathered, which the
# single-device tests shortcut past (8-device subprocess, cp_window style)
_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import all_configs, smoke_config
    from repro.serve.engine import Request, make_engine
    from repro.sharding.axes import ShardCtx, make_mesh

    cfg = smoke_config(all_configs()["mistral-nemo-12b"])
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (5, 11, 19)]

    def serve(ctx, **kw):
        eng = make_engine(cfg, ctx, max_slots=2, max_len=64,
                          decode_quantum=4, **kw)
        reqs = [Request(rid=i, prompt=p, max_new=12)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        return reqs

    # reference is the DENSE fast engine on the SAME mesh: sharded bf16
    # reductions already reorder vs 1-device (greedy argmax amplifies
    # that, dense path included), so the paging invariant is paged ≡
    # dense at identical sharding
    mesh = make_mesh((1, 4), ("data", "model"))
    ref = serve(ShardCtx(mesh=mesh))
    # 4-way model axis: page_size 8 → each shard owns 2 offsets per page
    got = serve(ShardCtx(mesh=mesh), paged=True, page_size=8)
    for a, b in zip(got, ref):
        assert a.done and a.out == b.out, (a.rid, a.out, b.out)
    print("PAGED-SHARD-OK")
""")


@pytest.mark.slow
def test_paged_model_sharded_matches_reference():
    r = subprocess.run([sys.executable, "-c", _SHARDED],
                       env=dict(os.environ, PYTHONPATH="src"),
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PAGED-SHARD-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_paged_rejects_data_parallel_mesh():
    """Pool pages are replicated over the batch axes — the engine must
    refuse rather than let replicas diverge (ROADMAP follow-on)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.configs import all_configs, smoke_config
        from repro.serve.engine import make_engine
        from repro.sharding.axes import ShardCtx, make_mesh
        cfg = smoke_config(all_configs()["mistral-nemo-12b"])
        ctx = ShardCtx(mesh=make_mesh((2, 4), ("data", "model")))
        try:
            make_engine(cfg, ctx, max_len=64, paged=True, page_size=8)
        except ValueError as e:
            assert "batch axis" in str(e), e
            print("PAGED-DP-REJECT-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, PYTHONPATH="src"),
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PAGED-DP-REJECT-OK" in r.stdout, (r.stdout[-2000:]
                                              + r.stderr[-2000:])


# ------------------------------------------------------- host-sync probe
@pytest.mark.parametrize("kw", [{}, {"paged": True, "page_size": 8},
                                {"temperature": 0.8, "top_k": 4}],
                         ids=["dense", "paged", "sampled"])
def test_single_host_fetch_per_quantum(ctx, monkeypatch, kw):
    """The fast path performs exactly ONE blocking device→host fetch per
    decode quantum (plus one per admitted prefill group) — including under
    paged decode and on-device sampling (PRNG key stays device-resident)."""
    cfg = _cfg()
    calls = {"n": 0}
    orig = engine_mod._host_fetch

    def probe(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(engine_mod, "_host_fetch", probe)
    eng, reqs = _serve(cfg, ctx, _prompts(cfg, [4, 9, 17]), 8, **kw)
    assert all(r.done for r in reqs)
    assert eng.quanta > 0 and eng.prefill_groups > 0
    assert calls["n"] == eng.quanta + eng.prefill_groups, (
        calls["n"], eng.quanta, eng.prefill_groups)


# ------------------------------------------------ graceful prompt limits
def test_submit_rejects_oversized_and_empty_prompts(ctx):
    cfg = _cfg()
    eng = make_engine(cfg, ctx, max_slots=2, max_len=32)
    with pytest.raises(PromptTooLongError):
        eng.submit(Request(rid=0, prompt=list(range(32)), max_new=4))
    with pytest.raises(ValueError):
        eng.submit(Request(rid=1, prompt=[], max_new=4))
    assert not eng.pending                  # rejected requests never queue


def test_bucket_len_typed_error():
    assert bucket_len(17, min_bucket=16, max_bucket=64) == 32
    with pytest.raises(ValueError):
        bucket_len(100, min_bucket=16, max_bucket=64)


# ----------------------------------------------------------- stall guard
def test_run_guard_is_proportional_and_loud(ctx):
    cfg = _cfg()
    eng = make_engine(cfg, ctx, max_slots=2, max_len=32, decode_quantum=4)
    reqs = [Request(rid=i, prompt=[1, 2, 3], max_new=8) for i in range(3)]
    for r in reqs:
        eng.pending.append(r)
    small = eng._guard_limit()
    eng.pending.extend(Request(rid=9 + i, prompt=[1], max_new=800)
                       for i in range(5))
    assert eng._guard_limit() > small       # scales with outstanding work
    eng.pending.clear()
    eng.step = lambda: None                 # simulate a scheduling bug
    with pytest.raises(EngineStallError):
        eng.run([Request(rid=99, prompt=[1, 2], max_new=4)])
