"""Serving correctness: prefill→decode ≡ full forward (per family), ring
buffers for sliding windows, engine end-to-end, whisper decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs, smoke_config
from repro.models.layers import logits_fn
from repro.models.model import model_defs
from repro.models.transformer import lm_hidden
from repro.serve.decode import decode_step, whisper_decode_step
from repro.serve.engine import Request, make_engine
from repro.serve.prefill import prefill, whisper_prefill
from repro.sharding import params as prm

FAMS = ["mistral-nemo-12b", "gemma2-2b", "h2o-danube-1.8b",
        "deepseek-v2-236b", "mamba2-130m", "jamba-v0.1-52b"]


@pytest.mark.parametrize("arch", FAMS)
def test_decode_matches_full_forward(arch, ctx):
    cfg = smoke_config(all_configs()[arch])
    params = prm.materialize(model_defs(cfg), jax.random.PRNGKey(0))
    B, S = 2, 48
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab)
    h, _ = lm_hidden(cfg, params, toks, ctx)
    ref = logits_fn(cfg, params["embed"], params["unembed"], h[:, -1:],
                    ctx)[:, 0]
    _, cache = prefill(cfg, params, toks[:, :S], ctx, max_len=S + 16)
    pos = jnp.full((B,), S, jnp.int32)
    logits, cache2 = decode_step(cfg, params, cache, toks[:, S], pos, ctx)
    rel = float(np.max(np.abs(np.array(logits) - np.array(ref)))) / \
        max(1e-9, float(np.max(np.abs(np.array(ref)))))
    assert rel < 3e-2, (arch, rel)
    # chained second step stays finite
    l2, _ = decode_step(cfg, params, cache2, toks[:, S], pos + 1, ctx)
    assert np.isfinite(np.array(l2)).all()


def test_sliding_window_ring_equivalence(ctx):
    """Decoding far past the window must match a fresh prefill of the same
    suffix (ring overwrite is exact)."""
    cfg = smoke_config(all_configs()["h2o-danube-1.8b"])  # window 32
    params = prm.materialize(model_defs(cfg), jax.random.PRNGKey(0))
    B, S, extra = 1, 40, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + extra), 0,
                              cfg.vocab)
    _, cache = prefill(cfg, params, toks[:, :S], ctx, max_len=96)
    logits = None
    for t in range(extra):
        pos = jnp.full((B,), S + t, jnp.int32)
        logits, cache = decode_step(cfg, params, cache, toks[:, S + t], pos,
                                    ctx)
    h, _ = lm_hidden(cfg, params, toks, ctx)
    ref = logits_fn(cfg, params["embed"], params["unembed"], h[:, -1:],
                    ctx)[:, 0]
    rel = float(np.max(np.abs(np.array(logits) - np.array(ref)))) / \
        max(1e-9, float(np.max(np.abs(np.array(ref)))))
    assert rel < 3e-2, rel


def test_whisper_prefill_decode(ctx):
    cfg = smoke_config(all_configs()["whisper-large-v3"])
    params = prm.materialize(model_defs(cfg), jax.random.PRNGKey(0))
    B, Se = 2, 32
    frames = jax.random.normal(jax.random.PRNGKey(1), (B, Se, cfg.d_model)) \
        * 0.1
    enc, cache = whisper_prefill(cfg, params, frames, ctx)
    assert enc.shape == (B, Se, cfg.d_model)
    tok = jnp.zeros((B,), jnp.int32)
    logits, cache = whisper_decode_step(cfg, params, cache, tok,
                                        jnp.zeros((B,), jnp.int32), ctx)
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.array(logits)).all()
    # greedy decode against the full decoder forward
    from repro.models.whisper import decode_hidden
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 5), 0, cfg.vocab)
    h = decode_hidden(cfg, params, toks, enc, ctx)
    ref = jnp.einsum("bd,dv->bv", h[:, -1],
                     params["embed"]["table"].T.astype(h.dtype))
    for t in range(5):
        logits, cache = whisper_decode_step(
            cfg, params, cache, toks[:, t], jnp.full((B,), t, jnp.int32), ctx)
    rel = float(np.max(np.abs(np.array(logits) - np.array(ref)))) / \
        max(1e-9, float(np.max(np.abs(np.array(ref)))))
    assert rel < 3e-2, rel


# ---------------------------------------------------- serving fast path
@pytest.mark.parametrize("arch", ["h2o-danube-1.8b",      # sliding window
                                  "mistral-nemo-12b",     # full attention
                                  "deepseek-v2-236b"])    # MLA
def test_bucketed_prefill_equivalence(arch, ctx):
    """Prefill padded to a power-of-2 bucket with explicit prompt_len must
    match exact-length prefill: same last-token logits, and (the ring-pack
    gather check) the same continuation tokens when decoding onward."""
    from repro.serve.decode import decode_loop

    cfg = smoke_config(all_configs()[arch])
    params = prm.materialize(model_defs(cfg), jax.random.PRNGKey(0))
    B, S, Sb, max_len = 2, 21, 32, 64
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    ref_logits, ref_cache = prefill(cfg, params, toks, ctx, max_len=max_len)
    padded = jnp.pad(toks, ((0, 0), (0, Sb - S)))
    pl = jnp.full((B,), S, jnp.int32)
    logits, cache = prefill(cfg, params, padded, ctx, max_len=max_len,
                            prompt_len=pl)
    # bf16 reduction order differs between S and Sb chunkings → repo-wide
    # 3e-2 relative tolerance (same metric as the decode-vs-forward tests)
    rel = float(np.max(np.abs(np.array(logits) - np.array(ref_logits)))) / \
        max(1e-9, float(np.max(np.abs(np.array(ref_logits)))))
    assert rel < 3e-2, (arch, rel)
    assert (np.array(logits).argmax(-1) ==
            np.array(ref_logits).argmax(-1)).all()
    # decode far enough past the window to exercise the ring wrap
    start = jnp.argmax(ref_logits, -1).astype(jnp.int32)
    args = (start, pl, jnp.ones(B, bool), jnp.full((B,), 99, jnp.int32))
    _, ref_toks, _ = decode_loop(cfg, params, ref_cache, *args, ctx,
                                 num_steps=12, eos_id=-1, max_len=max_len)
    _, fast_toks, _ = decode_loop(cfg, params, cache, *args, ctx,
                                  num_steps=12, eos_id=-1, max_len=max_len)
    np.testing.assert_array_equal(np.array(ref_toks), np.array(fast_toks))


def test_quantum_decode_equivalence(ctx):
    """N scanned decode steps ≡ N single decode steps (tokens and masking)."""
    from repro.serve.decode import decode_loop

    cfg = smoke_config(all_configs()["h2o-danube-1.8b"])
    params = prm.materialize(model_defs(cfg), jax.random.PRNGKey(0))
    B, S, N, max_len = 3, 12, 8, 64
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    logits, cache = prefill(cfg, params, toks, ctx, max_len=max_len)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    pos0 = jnp.full((B,), S, jnp.int32)
    remaining = jnp.asarray([N + 5, 4, N + 5], jnp.int32)  # row 1 stops early
    (_, _, pos, active, rem, _), loop_toks, loop_msks = decode_loop(
        cfg, params, cache, tok0, pos0, jnp.ones(B, bool), remaining, ctx,
        num_steps=N, eos_id=-1, max_len=max_len)
    # reference: single steps with host-side masking
    cache_s, tok, pos_s = cache, tok0, pos0
    ref = np.full((N, B), -1, np.int32)
    alive = np.ones(B, bool)
    budget = np.array(remaining)
    for t in range(N):
        logits, cache_s = decode_step(cfg, params, cache_s, tok, pos_s, ctx)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        ref[t, alive] = np.array(nxt)[alive]
        budget -= alive
        pos_s = pos_s + jnp.asarray(alive)
        alive = alive & (budget > 0)
        tok = jnp.where(jnp.asarray(alive), nxt, tok)
    np.testing.assert_array_equal(np.array(loop_toks), ref)
    assert np.array_equal(np.array(active), alive)
    assert np.array_equal(np.array(pos), np.array(pos_s))
    assert np.array_equal(np.array(rem), budget)


@pytest.mark.parametrize("paged", [False, True])
def test_engine_fast_matches_legacy(ctx, paged):
    """Same workload through the fast path and the reference path produces
    identical streams; fast prefill compiles once per bucket. With
    paged=True the fast engine serves from the shared page pool (on a
    full-attention arch, so the pool is actually exercised)."""
    arch = "mistral-nemo-12b" if paged else "h2o-danube-1.8b"
    cfg = smoke_config(all_configs()[arch])
    rng = np.random.default_rng(3)
    lens = [4, 5, 9, 17, 18, 23, 63]        # buckets: 16, 32, 64;
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in lens]
    # 63 = max_len-1: prefill fills the penultimate slot, exactly one decode
    # step remains — the boundary where fast/legacy done-checks must agree

    def serve(fast):
        kw = dict(paged=True, page_size=8) if paged and fast else {}
        eng = make_engine(cfg, ctx, max_slots=3, max_len=64, fast=fast,
                          decode_quantum=4, **kw)
        # max_new=1 finishes at prefill — both paths must stop there
        reqs = [Request(rid=i, prompt=p, max_new=1 if i == 1 else 6)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        return eng, reqs

    eng_f, fast = serve(True)
    _, legacy = serve(False)
    assert all(r.done for r in fast)
    for a, b in zip(fast, legacy):
        assert a.out == b.out, (a.rid, a.out, b.out)
    compiles = eng_f.prefill_compiles()
    assert compiles in (-1, 3), compiles   # one per bucket, not one per length


def test_engine_fast_mamba_exact_length_fallback(ctx):
    """Mamba mixers can't absorb pad tokens, so the fast engine falls back
    to exact-length (but still batched) prefill and stays correct."""
    cfg = smoke_config(all_configs()["mamba2-130m"])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (5, 5, 9)]

    def serve(fast):
        eng = make_engine(cfg, ctx, max_slots=2, max_len=48, fast=fast,
                          decode_quantum=3)
        assert eng.pad_safe is False
        reqs = [Request(rid=i, prompt=p, max_new=4)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        return reqs

    fast, legacy = serve(True), serve(False)
    for a, b in zip(fast, legacy):
        assert a.done and a.out == b.out, (a.rid, a.out, b.out)


def test_engine_continuous_batching(ctx):
    cfg = smoke_config(all_configs()["h2o-danube-1.8b"])
    eng = make_engine(cfg, ctx, max_slots=3, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6).tolist(),
                    max_new=5) for i in range(5)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.out) >= 5 for r in reqs)
    # determinism: same prompt → same continuation
    r2 = [Request(rid=9, prompt=reqs[0].prompt, max_new=5)]
    eng2 = make_engine(cfg, ctx, max_slots=3, max_len=64)
    eng2.run(r2)
    assert r2[0].out == reqs[0].out


# ------------------------------------------------------- on-device sampling
def test_sampling_determinism_and_greedy(ctx):
    """decode_loop sampling (ROADMAP "Real sampling"): temperature/top-k
    runs on device with the PRNG key as a scan carry — reproducible per
    seed, seed-sensitive, top_k=1 ≡ greedy, temperature=0 ≡ the default
    engine — and still exactly one host fetch per quantum."""
    cfg = smoke_config(all_configs()["mistral-nemo-12b"])
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (5, 9, 17)]

    def serve(**kw):
        eng = make_engine(cfg, ctx, max_slots=2, max_len=64,
                          decode_quantum=4, **kw)
        reqs = [Request(rid=i, prompt=p, max_new=8)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        return [r.out for r in reqs]

    greedy = serve()
    assert serve(temperature=0.0) == greedy            # static greedy path
    # top_k=1 collapses the categorical onto the argmax regardless of seed
    assert serve(temperature=0.7, top_k=1, sample_seed=5) == greedy
    a = serve(temperature=0.8, top_k=4, sample_seed=0)
    assert serve(temperature=0.8, top_k=4, sample_seed=0) == a
    assert serve(temperature=0.8, top_k=4, sample_seed=1) != a
    assert all(t >= 0 for out in a for t in out)       # real token ids
    # the FIRST token of a stream is sampled too (prefill argmax would pin
    # position 0 to greedy for every seed)
    hot = serve(temperature=5.0, sample_seed=2)
    assert [o[0] for o in hot] != [o[0] for o in greedy]


def test_sampling_engine_validation(ctx):
    cfg = smoke_config(all_configs()["mistral-nemo-12b"])
    with pytest.raises(ValueError):
        make_engine(cfg, ctx, temperature=-0.1)
    with pytest.raises(ValueError):
        make_engine(cfg, ctx, top_k=-1)
    with pytest.raises(ValueError):               # typed at construction,
        make_engine(cfg, ctx, top_k=cfg.vocab + 1)  # not a lax.top_k trace
    with pytest.raises(ValueError):               # legacy path is greedy
        make_engine(cfg, ctx, fast=False, temperature=0.5)


def test_serve_launcher_smoke(monkeypatch):
    """The serving launcher, as chip_smoke.py drives it, at CPU size:
    paged engine, every request finished with its full budget."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    eng, reqs = serve.main(["--smoke", "--requests", "3", "--max-new", "5"])
    assert eng.paged and eng.cfg.name.endswith("-smoke")
    assert all(r.done and len(r.out) == 5 for r in reqs)
    assert all(serve.PROMPT_LEN[0] <= len(r.prompt) <= serve.PROMPT_LEN[1]
               for r in reqs)
