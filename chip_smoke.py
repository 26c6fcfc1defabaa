"""Smoke run of the serving path on a TPU, at Mistral-NeMo-12B's published
widths. Proves the system starts on the chip and answers correctly; it
is not a benchmark, and the times it prints are not speed figures.

    python3 chip_smoke.py            # one chip: phases a-f below
    python3 chip_smoke.py --pool 4   # four one-chip replicas, nothing else

Model: ``mistral-nemo-12b`` with only ``n_layers`` cut from 40 to 8, one
stage of a 5-stage pipeline deployment; every width is as published
(d_model 5120, 32 query / 8 KV heads of 128, d_ff 14336, 131072 vocab).
Weights are random from a seed.

One chip:
  a. the device, as JAX reports it, and its HBM;
  b. the Pallas paged-attention kernel against its jnp oracle (``ref.py``)
     at the engine's decode shapes;
  c. prefill, then one decode step through a paged cache and the kernel,
     against the full forward's last-position logits (B 2, S 512);
  d. ``repro.launch.serve`` serves 8 seeded requests of 200-1000 prompt
     tokens and 64 new tokens each through ``Engine(paged=True)``, and its
     compiled decode loop must hold the kernel (``tpu_custom_call``);
  e. wall time, prefill compiles, compile seconds and peak HBM;
  f. last line: ``{"ok": true, "device": {...}}``.

``--pool N`` runs only the replica pool: a ``MultiEngine`` of N tiers,
tier i with its weights and cache on ``jax.devices()[i]``, against one
replica on chip 0 serving the same 16 requests. Greedy streams must be
identical, and every tier must place its leaves on its own chip and
serve work.

Exits non-zero, printing no result line, unless JAX's first device is a
TPU. Everything runs in this one process (the pool steps its tiers in
threads), since a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH, LAYERS, STAGES = "mistral-nemo-12b", 8, 5
SLOTS, MAX_LEN, PAGE, MAX_NEW = 8, 2048, 16, 64
SEED = 0
# phase b: kernel and oracle both reduce in float32 over bf16-exact
# inputs, so they differ only where the kernel's float32 dots round an
# operand (q·scale, the probabilities) to bf16 on the MXU: a relative
# 2^-8 per term, at most ~1e-2 on attention outputs of order 1. A wrong
# page, mask or offset moves them by order 1.
KERNEL_TOL = 2e-2
# phase c: the repo-wide bf16 bound on prefill+decode vs full forward
# (tests/test_serve.py): both paths run the same bf16 weights, but reduce
# in different orders and chunkings
LOGITS_RTOL = 3e-2


def require(ok: bool, what) -> None:
    """A check that ``python -O`` cannot strip, unlike ``assert``."""
    if not ok:
        raise AssertionError(what)


def _device_line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Backend compile seconds (persistent-cache reads included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def phase_device(dev) -> None:
    stats = dev.memory_stats() or {}
    print(f"[a] device: platform={dev.platform} kind={dev.device_kind} "
          f"HBM limit={stats.get('bytes_limit')} bytes, "
          f"in use={stats.get('bytes_in_use')} bytes")


def phase_kernel(cfg, dev) -> None:
    """Kernel vs oracle at 8 slots, the full 128-page table, positions
    from the first token to the last slot of max_len."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import ref
    from repro.kernels.paged_attention.paged_attention import \
        paged_flash_decode_gqa

    T = MAX_LEN // PAGE
    N = 1 + SLOTS * T
    hkv, grp, dh = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
        cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    with jax.default_device(dev):
        q = jax.random.normal(ks[0], (SLOTS, hkv, grp, dh), jnp.bfloat16)
        pk = jax.random.normal(ks[1], (N, PAGE, hkv, dh), jnp.bfloat16)
        pv = jax.random.normal(ks[2], (N, PAGE, hkv, dh), jnp.bfloat16)
        rng = np.random.default_rng(SEED)
        pt = jnp.asarray(1 + rng.permutation(N - 1).reshape(SLOTS, T),
                         jnp.int32)
        pos = jnp.asarray([0, PAGE - 1, PAGE, 777, 1023, 1024,
                           MAX_LEN - 2, MAX_LEN - 1], jnp.int32)
        kw = dict(page_size=PAGE, scale=dh ** -0.5)
        o, m, l = paged_flash_decode_gqa(q, pk, pv, pt, pos, 0, **kw)
        with jax.default_matmul_precision("highest"):
            o_r, m_r, l_r = jax.jit(
                ref.paged_flash_decode_gqa_ref,
                static_argnames=("page_size", "scale"))(
                    q, pk, pv, pt, pos, 0, **kw)
    err_o = float(jnp.max(jnp.abs(o / l[..., None] - o_r / l_r[..., None])))
    err_m = float(jnp.max(jnp.abs(m - m_r)))
    err_l = float(jnp.max(jnp.abs(l - l_r) / l_r))
    print(f"[b] paged kernel vs ref.py (B {SLOTS}, Hkv {hkv}, G {grp}, "
          f"dh {dh}, page {PAGE}, {T} pages): max abs err o/l {err_o:.3e}, "
          f"m {err_m:.3e}, max rel err l {err_l:.3e} (tol {KERNEL_TOL})")
    require(max(err_o, err_m, err_l) < KERNEL_TOL, (err_o, err_m, err_l))


def phase_reference(cfg, ctx) -> None:
    """Prefill 512 tokens, lay the rows into a page pool (trash page 0,
    then each row's pages, then one fresh page per row for position S),
    decode one token through the kernel; compare with the full forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.layers import logits_fn
    from repro.models.model import model_defs
    from repro.models.transformer import lm_hidden
    from repro.serve.decode import decode_step
    from repro.serve.prefill import prefill
    from repro.sharding import params as prm

    B, S = 2, 512
    n = S // PAGE
    params = prm.materialize_sharded(model_defs(cfg),
                                     jax.random.PRNGKey(SEED + 1), ctx)
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 2), (B, S + 1), 0,
                              cfg.vocab)

    @jax.jit
    def full(params, toks):
        h, _ = lm_hidden(cfg, params, toks, ctx)
        return logits_fn(cfg, params["embed"], params["unembed"],
                         h[:, -1:], ctx)[:, 0]

    @jax.jit
    def paged(params, toks):
        _, rows = prefill(cfg, params, toks[:, :S], ctx, max_len=MAX_LEN,
                          page_size=PAGE)

        def pool(r):                        # (L, B, S, …) → (L, N, PAGE, …)
            pages = r.reshape((r.shape[0], B * n, PAGE) + r.shape[3:])
            return jnp.concatenate(
                [jnp.zeros_like(pages[:, :1]), pages,
                 jnp.zeros_like(pages[:, :B])], axis=1)

        pt = jnp.concatenate([1 + jnp.arange(B * n).reshape(B, n),
                              (1 + B * n + jnp.arange(B))[:, None]], axis=1)
        pos = jnp.full((B,), S, jnp.int32)
        logits, _ = decode_step(cfg, params, jax.tree.map(pool, rows),
                                toks[:, S], pos, ctx, page_table=pt,
                                paged_kernel=True)
        return logits

    want = np.asarray(full(params, toks))
    got = np.asarray(paged(params, toks))
    require(got.shape == want.shape == (B, cfg.vocab),
            (got.shape, want.shape))
    require(np.isfinite(got).all() and np.isfinite(want).all(),
            "non-finite logits")
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    print(f"[c] prefill {S} + 1 paged decode step vs full forward "
          f"(B {B}): rel max err {rel:.3e} (tol {LOGITS_RTOL})")
    require(rel < LOGITS_RTOL, rel)


def phase_serve(cfg, clock: CompileClock, dev) -> None:
    from repro.launch import serve

    t0 = time.perf_counter()
    eng, reqs = serve.main(["--arch", ARCH, "--layers", str(LAYERS),
                            "--requests", "8", "--max-new", str(MAX_NEW),
                            "--max-slots", str(SLOTS),
                            "--max-len", str(MAX_LEN), "--seed", str(SEED)])
    wall = time.perf_counter() - t0
    require(eng.paged and eng.page_size == PAGE, "serve must run paged")
    for r in reqs:
        require(r.done and len(r.out) == MAX_NEW,
                (r.rid, r.done, len(r.out)))
        require(all(0 <= t < cfg.vocab for t in r.out), r.rid)
    hlo = eng.decode_hlo()
    require("tpu_custom_call" in hlo, "decode loop does not run the kernel")
    print(f"[d] served {len(reqs)} requests x {MAX_NEW} tokens, all done, "
          f"ids in vocab; compiled decode loop holds tpu_custom_call")
    stats = dev.memory_stats() or {}
    print(f"[e] smoke run, not a benchmark: serve wall {wall:.2f} s "
          f"(weights init and compiles included), "
          f"{eng.prefill_compiles()} prefill compiles, "
          f"compile {clock.secs:.1f} s over the whole run with "
          f"{clock.hits} persistent-cache hits, "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def phase_pool(cfg, n: int, devs) -> None:
    import jax
    from repro.launch.serve import PROMPT_LEN, make_requests
    from repro.serve.engine import Engine
    from repro.serve.multi_engine import make_multi_engine
    from repro.sharding.axes import single_device_ctx

    if len(devs) < n:
        raise SystemExit(f"--pool {n} needs {n} devices, JAX sees "
                         f"{len(devs)}")
    ctxs = [single_device_ctx(d) for d in devs[:n]]
    kw = dict(max_slots=SLOTS, max_len=MAX_LEN, paged=True,
              paged_kernel=True)
    meng = make_multi_engine(
        cfg, ctxs[0], [{"name": f"chip{i}", "ctx": c}
                       for i, c in enumerate(ctxs)], seed=SEED, **kw)
    reqs = make_requests(cfg, 16, PROMPT_LEN, MAX_NEW, SEED)
    t0 = time.perf_counter()
    meng.run(reqs)
    wall = time.perf_counter() - t0
    for i, tier in enumerate(meng.tiers):
        leaves = (jax.tree.leaves(tier.engine.params)
                  + jax.tree.leaves(tier.engine.cache))
        require(all(x.devices() == {devs[i]} for x in leaves), tier.name)
    routed = {t.name: t.routed for t in meng.tiers}
    print(f"[pool] routed per tier: {routed}; each tier's weights and "
          f"cache on its own device")
    require(all(routed.values()), routed)
    # one replica on chip 0, sharing tier 0's weights (read-only)
    single = Engine(cfg, meng.tiers[0].engine.params, ctxs[0], **kw)
    ref = make_requests(cfg, 16, PROMPT_LEN, MAX_NEW, SEED)
    single.run(ref)
    for a, b in zip(reqs, ref):
        require(a.done and b.done and a.out == b.out, (a.rid, a.out, b.out))
    print(f"[pool] {len(reqs)} greedy streams identical to one replica "
          f"on chip 0; smoke run, not a benchmark: pool wall {wall:.2f} s "
          f"including compiles")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--pool", type=int, default=0, metavar="N",
                    help="run only the N-replica pool, one replica per chip")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    from repro.launch.serve import build_config
    from repro.sharding.axes import single_device_ctx

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devs[0].platform}",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    cfg = build_config(ARCH, layers=LAYERS)
    print(f"model {ARCH}: n_layers {LAYERS} of 40 (one stage of a "
          f"{STAGES}-stage pipeline), widths as published; compile cache "
          f"{cache_dir}")
    if args.pool:
        phase_pool(cfg, args.pool, devs)
    else:
        phase_device(devs[0])
        phase_kernel(cfg, devs[0])
        phase_reference(cfg, single_device_ctx(devs[0]))
        phase_serve(cfg, clock, devs[0])
    print(json.dumps({"ok": True, "device": _device_line(devs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
