"""Serving fast-path benchmark: fused quantum decode + bucketed batched
prefill + cache donation vs. the reference per-token engine, plus the paged
KV cache vs. dense per-slot rows.

    PYTHONPATH=src python -m benchmarks.bench_serve

Measures, on the SAME workload (mixed prompt lengths so the legacy path
recompiles per length):
  * tokens/sec end-to-end (compiles included — recompile overhead is the
    point) for fast and legacy engines, and their ratio;
  * prefill compile count (jit cache probe): fast = one per length bucket,
    legacy = one per distinct prompt length;
  * per-cycle scheduler balance: mean admitted prompts vs. decoded tokens
    per engine cycle and the final HBB `f` ratio;
  * memory: reserved KV-cache bytes (paged pool vs dense rows, sized for
    the same workload) and the max context a single request could grow to
    inside the dense engine's HBM budget.

The paged-vs-dense comparison runs on a full-attention arch (mistral-nemo)
— sliding-window archs keep their O(window) rings and would not exercise
the pool.

PR 3 adds the paged-*kernel* comparison (BENCH_2.json): the same paged
workload through the in-kernel page-table walk (`paged_kernel=True` — on
CPU smoke this is the XLA-fused blockwise reference of the kernel
contract, attending only the live page prefix; on TPU the Pallas kernel)
vs. the PR 2 jnp gathered-view path, on an engine provisioned for long
contexts (`KERNEL_MAX_LEN`), where the gather path pays O(max_len) per
token and the kernel path pays O(context). Plus a long-context row — a
request whose context cannot fit the dense engine's 64-token rows at all.

PR 4 adds the multi-tier comparison (BENCH_3.json): a heterogeneous
MultiEngine pool — a short-context dense tier (many small slots) plus a
long-context paged tier (few large slots; long slots are HBM-expensive) —
serving a mixed short+long workload vs. the best single tier that can
serve the whole workload alone (the long tier; the short tier raises
PromptTooLongError on the long prompts). The pool wins structurally: the
long tier alone must push the short flood through its 2 slots in quanta
whose live-page width follows the resident long contexts, while the pool
keeps shorts on the cheap tier and routes by measured per-tier tok/s
(proportional_split). Token streams stay equivalent to a single engine at
temperature=0.

PR 5 adds the speculative-decode comparison (BENCH_4.json): a big/little
pair — an 8-layer softened target and its first layer as the draft
(`models/draft.py`) — vs. the SAME target serving alone, at k ∈ {2,4,8}
greedy plus acceptance-by-temperature at k=4. The win is structural: k
cheap draft steps plus ONE batched (k+1)-position verify replace up to
k+1 serial target steps, so it shows even on the serializing CPU smoke
box; greedy streams are asserted token-identical to target-only.

PR 6 adds the degraded-mode comparison (BENCH_5.json): the same
dense+paged pool twice, healthy vs. losing its paged tier to injected
step failures mid-run (`serve/faults.py`, DESIGN.md §8). The degraded
run must still finish every request with byte-identical greedy streams
and zero leaked pages; the artifact records the degraded/healthy
throughput ratio, retry/reclaim counts, and the quarantine→healthy
recovery cycle count.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

MAX_SLOTS = 4
MAX_LEN = 64
PAGE_SIZE = 8
# the kernel-vs-gather rows run on a long-context-provisioned engine: the
# table is 1024/8 = 128 pages wide while the workload's contexts stay small
KERNEL_MAX_LEN = 1024
LONG_PROMPT = 400
LONG_MAX_NEW = 40
# multi-tier pool shape (BENCH_3): many small short-context slots + few
# HBM-expensive long-context slots
MT_SHORT_REQS = 20
MT_LONG_SLOTS = 2


def _workload(cfg, n_requests: int, max_new: int, seed: int = 0,
              lens: list[int] | None = None):
    rng = np.random.default_rng(seed)
    if lens is None:
        # many distinct lengths across two power-of-2 buckets (≤16, ≤32)
        lens = [int(x) for x in rng.integers(4, 31, n_requests)]
    return [(i, rng.integers(0, cfg.vocab, n).tolist()) for i, n in
            enumerate(lens)]


def _workload_pool_pages(workload, max_new: int, decode_quantum: int,
                         max_slots: int = MAX_SLOTS, max_len: int = MAX_LEN,
                         page_size: int = PAGE_SIZE) -> int:
    """Pool sized to the workload's worst case (+ the reserved trash page)
    instead of max_slots × max_len — the memory the paged engine banks."""
    from repro.serve.engine import worst_case_pages

    max_prompt = max(len(p) for _, p in workload)
    return 1 + max_slots * worst_case_pages(max_prompt, max_new,
                                            decode_quantum, max_len,
                                            page_size)


def serve_once(mode: str, *, arch: str = "h2o-danube-1.8b",
               n_requests: int = 12, max_new: int = 16,
               decode_quantum: int = 8, seed: int = 0,
               warmup: bool = False, reps: int = 1,
               max_slots: int = MAX_SLOTS, max_len: int = MAX_LEN,
               page_size: int = PAGE_SIZE, paged_kernel=True,
               lens: list[int] | None = None) -> dict:
    """mode: "fast" | "legacy" | "paged". `warmup` pre-runs a small workload
    so the timed pass measures steady state (used for the paged-vs-dense
    memory comparison, where compile counts are identical by construction
    and the interesting number is the per-token cost of page indirection);
    `reps` re-runs the timed workload and keeps the fastest pass (host
    scheduling noise dwarfs the per-token delta on CPU smoke). `lens`
    overrides the request lengths (long-context row); `paged_kernel`
    selects the in-kernel table walk vs. the jnp gather escape hatch."""
    from repro.configs import get_config, smoke_config
    from repro.serve.engine import Request, make_engine
    from repro.sharding.axes import single_device_ctx

    cfg = smoke_config(get_config(arch))
    ctx = single_device_ctx()
    work = _workload(cfg, n_requests, max_new, seed, lens=lens)
    warm_work = _workload(cfg, 4, max_new, seed + 1) if warmup else []
    kw = {}
    if mode == "paged":
        # size for the timed workload AND the (slightly longer) warmup pass;
        # the allocator insists one full max_len context must always fit
        pages = _workload_pool_pages(work + warm_work, max_new + 1,
                                     decode_quantum, max_slots, max_len,
                                     page_size)
        kw = dict(paged=True, page_size=page_size, paged_kernel=paged_kernel,
                  num_pages=max(pages, 1 + max_len // page_size))
    eng = make_engine(cfg, ctx, max_slots=max_slots, max_len=max_len,
                      fast=mode != "legacy", decode_quantum=decode_quantum,
                      **kw)
    if warmup:
        eng.run([Request(rid=-1 - i, prompt=p, max_new=max_new + 1)
                 for i, p in warm_work])
    dt = float("inf")
    q0, admitted, decoded = eng.quanta, 0, 0
    for rep in range(max(1, reps)):
        reqs = [Request(rid=1000 * rep + i, prompt=p, max_new=max_new)
                for i, p in work]
        t0 = time.perf_counter()
        eng.run(reqs)
        dt = min(dt, time.perf_counter() - t0)
        admitted += len(reqs)
        decoded += sum(max(len(r.out) - 1, 0) for r in reqs)
    tok = sum(len(r.out) for r in reqs)
    # per decode quantum of the timed passes (the legacy path counts none)
    quanta = eng.quanta - q0
    return {
        "mode": mode,
        "arch": arch,
        "tok": tok,
        "dt": dt,
        "tok_s": tok / dt,
        "prefill_compiles": eng.prefill_compiles(),
        "distinct_prompt_lens": len({len(r.prompt) for r in reqs}),
        "f": eng.tracker.f(),
        "reserved_cache_bytes": eng.reserved_cache_bytes(),
        "mean_admitted_per_cycle": admitted / quanta if quanta else None,
        "mean_decoded_per_cycle": decoded / quanta if quanta else None,
        "cycles": quanta,
        "all_done": all(r.done for r in reqs),
    }


def paged_rows(**kw) -> list[dict]:
    """Dense-fast vs paged on a full-attention arch, with memory columns."""
    from repro.configs import get_config, smoke_config
    from repro.serve.kv_cache import page_bytes

    kw.setdefault("arch", "mistral-nemo-12b")
    kw.setdefault("warmup", True)
    kw.setdefault("reps", 3)
    dense = serve_once("fast", **kw)
    paged = serve_once("paged", **kw)
    paged["tok_s_vs_dense"] = paged["tok_s"] / max(dense["tok_s"], 1e-9)
    cfg = smoke_config(get_config(kw["arch"]))
    # longest context one request could occupy inside the DENSE engine's
    # cache budget, were it granted every page (page-table width permitting)
    per_page = max(1, page_bytes(cfg, PAGE_SIZE))
    paged["max_ctx_at_dense_hbm"] = (
        (dense["reserved_cache_bytes"] // per_page - 1) * PAGE_SIZE)
    dense["max_ctx_at_dense_hbm"] = MAX_LEN      # one dense row, fixed
    return [dense, paged]


def kernel_rows(**kw) -> list[dict]:
    """In-kernel page-table walk vs. the jnp gathered view — both paged, on
    an engine provisioned for long contexts (table width
    KERNEL_MAX_LEN/PAGE_SIZE pages) serving the short-prompt smoke
    workload. The gather path materializes and attends the full table
    width for every token; the kernel path walks only the live page
    prefix, so its per-token cost follows the context, not the
    provisioning."""
    kw.setdefault("arch", "mistral-nemo-12b")
    kw.setdefault("max_len", KERNEL_MAX_LEN)
    kw.setdefault("warmup", True)
    kw.setdefault("reps", 3)
    gather = serve_once("paged", paged_kernel=False, **kw)
    kern = serve_once("paged", **kw)
    kern["tok_s_vs_gather"] = kern["tok_s"] / max(gather["tok_s"], 1e-9)
    gather["tok_s_vs_gather"] = 1.0
    return [kern, gather]


def long_ctx_row(**kw) -> dict:
    """One request whose context (LONG_PROMPT + LONG_MAX_NEW tokens) cannot
    exist under the dense engine's MAX_LEN-token rows at any slot count —
    the PR 2 capacity win, now decoded through the kernel path. Reports the
    pool actually reserved vs. what dense rows at the same provisioned
    max_len would cost."""
    from repro.configs import get_config, smoke_config
    from repro.serve.kv_cache import cache_bytes

    kw.setdefault("arch", "mistral-nemo-12b")
    # rep 1 absorbs the 512-bucket prefill compile; best-of keeps the warm rep
    kw.setdefault("reps", 2)
    row = serve_once("paged", max_len=KERNEL_MAX_LEN,
                     lens=[LONG_PROMPT, 9, 17], max_new=LONG_MAX_NEW, **kw)
    cfg = smoke_config(get_config(kw["arch"]))
    row["ctx"] = LONG_PROMPT + LONG_MAX_NEW
    row["dense_max_ctx"] = MAX_LEN
    row["dense_equiv_cache_bytes"] = cache_bytes(cfg, MAX_SLOTS,
                                                 KERNEL_MAX_LEN, 1)
    return row


def _mt_workload(cfg, seed: int = 0):
    """Mixed traffic: a flood of short prompts plus two long prompts that
    only the long-context tier can hold."""
    rng = np.random.default_rng(seed)
    lens = [int(x) for x in rng.integers(4, 31, MT_SHORT_REQS)]
    lens += [LONG_PROMPT, LONG_PROMPT - 27]
    prng = np.random.default_rng(seed + 1)
    return [(i, prng.integers(0, cfg.vocab, n).tolist()) for i, n in
            enumerate(lens)]


def multi_tier_rows(*, arch: str = "mistral-nemo-12b", max_new: int = 16,
                    decode_quantum: int = 8, reps: int = 3,
                    seed: int = 0) -> list[dict]:
    """Heterogeneous tier pool vs. the best single tier (BENCH_3).

    Tiers: `short` — dense fast engine, MAX_LEN-token slots, MAX_SLOTS of
    them; `long` — paged-kernel engine provisioned for KERNEL_MAX_LEN
    contexts with MT_LONG_SLOTS slots (a long slot's page budget is ~16×
    a whole short slot, so few of them is the honest provisioning). The
    short tier cannot serve the long prompts at all, so the best — only —
    single-tier baseline is the long tier serving everything. Interleaved
    best-of-`reps` timing so both rows see the same host-noise regime;
    outputs are checked token-identical per request (greedy streams must
    not depend on the serving tier)."""
    from repro.configs import get_config, smoke_config
    from repro.serve.engine import (Request, make_engine, worst_case_pages)
    from repro.serve.multi_engine import make_multi_engine
    from repro.sharding.axes import single_device_ctx

    cfg = smoke_config(get_config(arch))
    ctx = single_device_ctx()
    work = _mt_workload(cfg, seed)

    def make_reqs(rep: int) -> list:
        return [Request(rid=1000 * rep + i, prompt=p,
                        max_new=max_new if len(p) < MAX_LEN
                        else LONG_MAX_NEW)
                for i, p in work]

    pages = max(1 + MT_LONG_SLOTS * worst_case_pages(
        LONG_PROMPT, LONG_MAX_NEW + 1, decode_quantum, KERNEL_MAX_LEN,
        PAGE_SIZE), 1 + KERNEL_MAX_LEN // PAGE_SIZE)
    long_kw = dict(paged=True, page_size=PAGE_SIZE, num_pages=pages,
                   max_len=KERNEL_MAX_LEN, max_slots=MT_LONG_SLOTS)
    single_long = make_engine(cfg, ctx, decode_quantum=decode_quantum,
                              **long_kw)
    meng = make_multi_engine(cfg, ctx, [
        {"name": "short", "max_len": MAX_LEN, "max_slots": MAX_SLOTS},
        {"name": "long", **long_kw},
    ], decode_quantum=decode_quantum, seed=0)
    runners = {"single_long": single_long.run, "multi_tier": meng.run}
    for run in runners.values():                   # absorb compiles
        run(make_reqs(99))
    best = {k: float("inf") for k in runners}
    tok, outs, done = {}, {}, {}
    routed = {}
    for rep in range(max(1, reps)):
        for name, run in runners.items():
            if name == "multi_tier":       # per-rep routing counts, not the
                r0 = {t.name: t.routed for t in meng.tiers}  # running total
            reqs = make_reqs(rep)
            t0 = time.perf_counter()
            run(reqs)
            dt = time.perf_counter() - t0
            if name == "multi_tier":
                routed = {t.name: t.routed - r0[t.name] for t in meng.tiers}
            best[name] = min(best[name], dt)
            tok[name] = sum(len(r.out) for r in reqs)
            outs[name] = [r.out for r in reqs]
            done[name] = done.get(name, True) and all(r.done for r in reqs)
    equiv = outs["multi_tier"] == outs["single_long"]
    stats = meng.stats()
    multi = {
        "mode": "multi_tier",
        "arch": arch,
        "tok": tok["multi_tier"],
        "dt": best["multi_tier"],
        "tok_s": tok["multi_tier"] / best["multi_tier"],
        "tiers": {n: {"routed": routed[n], "tok_s": s["tok_s"],
                      "unit_cost": s["unit_cost"]}
                  for n, s in stats["tiers"].items()},
        "token_equiv": bool(equiv),
        "all_done": bool(done["multi_tier"]),
        "reserved_cache_bytes": sum(t.engine.reserved_cache_bytes()
                                    for t in meng.tiers),
    }
    single = {
        "mode": "single_long",
        "arch": arch,
        "tok": tok["single_long"],
        "dt": best["single_long"],
        "tok_s": tok["single_long"] / best["single_long"],
        "all_done": bool(done["single_long"]),
        "reserved_cache_bytes": single_long.reserved_cache_bytes(),
    }
    multi["tok_s_vs_best_single"] = multi["tok_s"] / max(single["tok_s"],
                                                         1e-9)
    return [multi, single]


def multi_csv_rows(mt: list[dict]) -> list[str]:
    """Harness-contract rows for the multi-tier pool (BENCH_3)."""
    lines = []
    for r in mt:
        us = r["dt"] / max(r["tok"], 1) * 1e6
        lines.append(f"serve/{r['mode']}/tok_s,{us:.0f},{r['tok_s']:.1f}")
    lines.append(f"serve/multi_tier_vs_best_single,0,"
                 f"{mt[0]['tok_s_vs_best_single']:.2f}")
    lines.append(f"serve/multi_tier/token_equiv,0,"
                 f"{int(mt[0]['token_equiv'])}")
    return lines


def write_bench3_json(mt: list[dict],
                      path: str | Path = "BENCH_3.json") -> None:
    """PR 4 perf artifact: heterogeneous tier pool vs. best single tier."""
    multi, single = mt
    doc = {
        "bench": "multi_tier_serving",
        "arch": multi["arch"] + " (smoke)",
        "tiers": multi["tiers"],
        "workload": {"short_requests": MT_SHORT_REQS, "long_requests": 2,
                     "long_prompt": LONG_PROMPT,
                     "long_max_new": LONG_MAX_NEW},
        "multi_tok_s": multi["tok_s"],
        "best_single_tier": "long",
        "best_single_tok_s": single["tok_s"],
        "multi_vs_best_single": multi["tok_s_vs_best_single"],
        "multi_reserved_cache_bytes": multi["reserved_cache_bytes"],
        "single_reserved_cache_bytes": single["reserved_cache_bytes"],
        "token_equiv": multi["token_equiv"],
        "all_done": bool(multi["all_done"] and single["all_done"]),
    }
    Path(path).write_text(json.dumps(doc, indent=2) + "\n")


# ------------------------------------------------- speculative decode (PR 5)
SPEC_KS = (2, 4, 8)
SPEC_TEMPS = (0.0, 0.5, 1.0)
SPEC_TARGET_LAYERS = 8
SPEC_ALPHA = 0.2


def spec_decode_rows(*, arch: str = "mistral-nemo-12b", max_new: int = 40,
                     decode_quantum: int = 4, reps: int = 3,
                     seed: int = 0) -> dict:
    """Speculative big/little decode vs. target-only (BENCH_4).

    The pair is built the honest way for a smoke box (DESIGN.md §7): the
    target is an `SPEC_TARGET_LAYERS`-deep GQA model whose deep-layer
    residual contributions are softened (`soften_deep_layers`,
    ×SPEC_ALPHA on layers ≥ 1), the draft is its first layer
    (`draft_from_target` — shared embeddings, so vocab-aligned by
    construction). The softened target IS the model both rows serve, so
    the comparison is apples-to-apples: the speedup is structural (k
    draft steps at ~1/8 cost + one batched K-position verify replace up
    to k+1 serial target steps), not a model downgrade, and the
    greedy streams must be token-identical. Greedy rows at k ∈ SPEC_KS;
    acceptance-by-temperature at k=4 shows the rate the router's effective
    tok/s scales by. One engine per row, reused across best-of-`reps`
    timed passes after a compile-absorbing warmup run."""
    import jax

    from repro.configs import get_config, smoke_config
    from repro.models.draft import draft_from_target, soften_deep_layers
    from repro.models.model import model_defs
    from repro.serve.engine import Engine, Request
    from repro.sharding import params as prm
    from repro.sharding.axes import single_device_ctx
    import dataclasses

    cfg = dataclasses.replace(smoke_config(get_config(arch)),
                              n_layers=SPEC_TARGET_LAYERS)
    ctx = single_device_ctx()
    params = prm.materialize(model_defs(cfg), jax.random.PRNGKey(seed))
    params = soften_deep_layers(cfg, params, 1, SPEC_ALPHA)
    dcfg, dparams = draft_from_target(cfg, params, 1)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, int(n)).tolist()
               for n in (5, 9, 11, 14, 7, 12)]        # one 16-token bucket

    def bench(**kw):
        eng = Engine(cfg, params, ctx, max_slots=4, max_len=MAX_LEN,
                     decode_quantum=decode_quantum, **kw)

        def mk(rep):
            return [Request(rid=1000 * rep + i, prompt=list(p),
                            max_new=max_new) for i, p in enumerate(prompts)]
        eng.run(mk(99))                               # absorb compiles
        a0, p0 = eng.spec_accepted, eng.spec_proposed
        best, outs, tok, done = float("inf"), None, 0, True
        for rep in range(max(1, reps)):
            reqs = mk(rep)
            t0 = time.perf_counter()
            eng.run(reqs)
            best = min(best, time.perf_counter() - t0)
            outs = [r.out for r in reqs]
            tok = sum(len(r.out) for r in reqs)
            done = done and all(r.done for r in reqs)
        prop = eng.spec_proposed - p0
        return {
            "tok": tok, "dt": best, "tok_s": tok / best,
            "acceptance": ((eng.spec_accepted - a0) / prop if prop else 0.0),
            "outs": outs, "all_done": done,
        }

    base = bench()
    rows = []
    for k in SPEC_KS:
        r = bench(draft_cfg=dcfg, draft_params=dparams, spec_k=k)
        r.update(mode=f"spec_k{k}", spec_k=k,
                 speedup=r["tok_s"] / max(base["tok_s"], 1e-9),
                 token_equiv=r.pop("outs") == base["outs"])
        rows.append(r)
    accept_by_t = {}
    for t in SPEC_TEMPS:
        if t == 0.0:
            accept_by_t["0.0"] = rows[SPEC_KS.index(4)]["acceptance"]
            continue
        r = bench(draft_cfg=dcfg, draft_params=dparams, spec_k=4,
                  temperature=t, sample_seed=seed)
        accept_by_t[str(t)] = r["acceptance"]
    base["mode"] = "target_only"
    base.pop("outs")
    return {"arch": arch, "base": base, "rows": rows,
            "acceptance_by_temperature": accept_by_t}


def spec_csv_rows(sp: dict) -> list[str]:
    """Harness-contract rows for speculative decode (BENCH_4)."""
    lines = []
    for r in [sp["base"]] + sp["rows"]:
        us = r["dt"] / max(r["tok"], 1) * 1e6
        lines.append(f"serve/{r['mode']}/tok_s,{us:.0f},{r['tok_s']:.1f}")
    k4 = next(r for r in sp["rows"] if r["spec_k"] == 4)
    lines.append(f"serve/spec_k4_vs_target_only,0,{k4['speedup']:.2f}")
    lines.append(f"serve/spec_k4/acceptance,0,{k4['acceptance']:.3f}")
    equiv = all(r["token_equiv"] for r in sp["rows"])
    lines.append(f"serve/spec/token_equiv,0,{int(equiv)}")
    return lines


def write_bench4_json(sp: dict, path: str | Path = "BENCH_4.json") -> None:
    """PR 5 perf artifact: speculative decode vs target-only."""
    k4 = next(r for r in sp["rows"] if r["spec_k"] == 4)
    doc = {
        "bench": "speculative_decode",
        "arch": sp["arch"] + f" (smoke, {SPEC_TARGET_LAYERS} layers, deep "
                             f"residuals ×{SPEC_ALPHA})",
        "draft": "first target layer, shared embeddings",
        "target_only_tok_s": sp["base"]["tok_s"],
        "rows": [{k: v for k, v in r.items() if k != "outs"}
                 for r in sp["rows"]],
        "speedup_k4": k4["speedup"],
        "acceptance_by_temperature": sp["acceptance_by_temperature"],
        "token_equiv": all(r["token_equiv"] for r in sp["rows"]),
        "all_done": bool(sp["base"]["all_done"]
                         and all(r["all_done"] for r in sp["rows"])),
    }
    Path(path).write_text(json.dumps(doc, indent=2) + "\n")


# ------------------------------------------------- degraded-mode pool (PR 6)
def fault_rows(*, arch: str = "mistral-nemo-12b", max_new: int = 16,
               decode_quantum: int = 4, n_requests: int = 10,
               seed: int = 0) -> dict:
    """Degraded-mode serving (BENCH_5): the same dense+paged pool, once
    healthy and once losing its paged tier to injected step failures
    mid-run (DESIGN.md §8). The degraded run must still complete every
    request with byte-identical greedy streams and zero page leaks —
    recovery costs wall clock, never tokens. Reported: degraded/healthy
    throughput ratio and the cycle count from quarantine to restored
    health."""
    from repro.configs import get_config, smoke_config
    from repro.serve.engine import Request
    from repro.serve.faults import Fault, FaultyEngine
    from repro.serve.multi_engine import HealthPolicy, make_multi_engine
    from repro.sharding.axes import single_device_ctx

    cfg = smoke_config(get_config(arch))
    ctx = single_device_ctx()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, int(n)).tolist()
               for n in rng.integers(4, 31, n_requests)]

    def make_reqs(rep: int) -> list:
        return [Request(rid=1000 * rep + i, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]

    def make_pool():
        return make_multi_engine(cfg, ctx, [
            {"name": "dense"},
            {"name": "paged", "paged": True, "page_size": PAGE_SIZE},
        ], max_slots=4, max_len=MAX_LEN, decode_quantum=decode_quantum,
            seed=0, concurrent=False,
            policy=HealthPolicy(quarantine_after=2, quarantine_cycles=2,
                                probation_steps=1, retry_backoff=1))

    healthy = make_pool()
    healthy.run(make_reqs(99))                     # absorb compiles
    h_reqs = make_reqs(0)
    t0 = time.perf_counter()
    healthy.run(h_reqs)
    h_dt = time.perf_counter() - t0

    faulted = make_pool()
    faulted.run(make_reqs(98))                     # same warm state
    sick = faulted.tiers[1]
    sick.engine = FaultyEngine(sick.engine,
                               [Fault(kind="raise", at=(2,), n=2)])
    f_reqs = make_reqs(0)
    t0 = time.perf_counter()
    faulted.run(f_reqs)
    f_dt = time.perf_counter() - t0

    raw = sick.engine.engine                       # unwrap the fault proxy
    leaked = raw.alloc.usable_pages - len(raw.alloc.free)
    quarantined_at = next((h["cycle"] for h in faulted.health_log
                           if h["to"] == "quarantined"), -1)
    recovered_at = next((h["cycle"] for h in faulted.health_log
                         if h["to"] == "healthy"), -1)
    h_tok = sum(len(r.out) for r in h_reqs)
    f_tok = sum(len(r.out) for r in f_reqs)
    return {
        "arch": arch,
        "healthy": {"tok": h_tok, "dt": h_dt, "tok_s": h_tok / h_dt,
                    "all_done": all(r.done for r in h_reqs)},
        "faulted": {"tok": f_tok, "dt": f_dt, "tok_s": f_tok / f_dt,
                    "all_done": all(r.done for r in f_reqs),
                    "retries": faulted.retries,
                    "reclaims": sick.reclaims,
                    "dead_letters": len(faulted.dead_letters),
                    "injected": len(sick.engine.fault_log)},
        "degraded_ratio": (f_tok / f_dt) / max(h_tok / h_dt, 1e-9),
        "token_equiv": [r.out for r in f_reqs] == [r.out for r in h_reqs],
        "leaked_pages": int(leaked),
        "recovery_cycles": (recovered_at - quarantined_at
                            if recovered_at >= 0 and quarantined_at >= 0
                            else -1),
        "health_log": faulted.health_log,
    }


def fault_csv_rows(ft: dict) -> list[str]:
    """Harness-contract rows for degraded-mode serving (BENCH_5)."""
    lines = []
    for mode in ("healthy", "faulted"):
        r = ft[mode]
        us = r["dt"] / max(r["tok"], 1) * 1e6
        lines.append(f"serve/{mode}_pool/tok_s,{us:.0f},{r['tok_s']:.1f}")
    lines.append(f"serve/faulted_vs_healthy,0,{ft['degraded_ratio']:.2f}")
    lines.append(f"serve/faulted/token_equiv,0,{int(ft['token_equiv'])}")
    lines.append(f"serve/faulted/leaked_pages,0,{ft['leaked_pages']}")
    lines.append(f"serve/faulted/recovery_cycles,0,{ft['recovery_cycles']}")
    return lines


def write_bench5_json(ft: dict, path: str | Path = "BENCH_5.json") -> None:
    """PR 6 perf artifact: degraded-mode pool vs. its healthy twin."""
    doc = {
        "bench": "fault_tolerant_serving",
        "arch": ft["arch"] + " (smoke)",
        "fault": "paged tier step raises at engine steps 2-3 (injected)",
        "healthy_tok_s": ft["healthy"]["tok_s"],
        "faulted_tok_s": ft["faulted"]["tok_s"],
        "degraded_ratio": ft["degraded_ratio"],
        "retries": ft["faulted"]["retries"],
        "reclaims": ft["faulted"]["reclaims"],
        "dead_letters": ft["faulted"]["dead_letters"],
        "token_equiv": ft["token_equiv"],
        "leaked_pages": ft["leaked_pages"],
        "recovery_cycles": ft["recovery_cycles"],
        "health_transitions": ft["health_log"],
        "all_done": bool(ft["healthy"]["all_done"]
                         and ft["faulted"]["all_done"]),
    }
    Path(path).write_text(json.dumps(doc, indent=2) + "\n")


def rows(**kw) -> list[dict]:
    fast = serve_once("fast", **kw)
    legacy = serve_once("legacy", **kw)
    fast["speedup_vs_legacy"] = fast["tok_s"] / max(legacy["tok_s"], 1e-9)
    legacy["speedup_vs_legacy"] = 1.0
    return [fast, legacy]


def csv_rows(out: list[dict], mem: list[dict] | None) -> list[str]:
    """Harness-contract ``name,us_per_call,derived`` rows (shared with
    benchmarks/run.py so the two emitters can't drift). `mem` is None when
    the paged comparison is unavailable."""
    lines = []
    for r in out:
        us = r["dt"] / max(r["tok"], 1) * 1e6
        lines.append(f"serve/{r['mode']}/tok_s,{us:.0f},{r['tok_s']:.1f}")
        lines.append(f"serve/{r['mode']}/prefill_compiles,{us:.0f},"
                     f"{r['prefill_compiles']}")
    lines.append(f"serve/speedup_fast_over_legacy,0,"
                 f"{out[0]['speedup_vs_legacy']:.2f}")
    for r in mem or []:
        us = r["dt"] / max(r["tok"], 1) * 1e6
        lines.append(f"serve/mem/{r['mode']}/reserved_cache_kb,{us:.0f},"
                     f"{r['reserved_cache_bytes'] / 1024:.1f}")
        lines.append(f"serve/mem/{r['mode']}/max_ctx_at_dense_hbm,{us:.0f},"
                     f"{r['max_ctx_at_dense_hbm']}")
    if mem:
        lines.append(f"serve/mem/paged_tok_s_vs_dense,0,"
                     f"{mem[1]['tok_s_vs_dense']:.2f}")
    return lines


def kernel_csv_rows(kern: list[dict], long_row: dict) -> list[str]:
    """Harness-contract rows for the paged-kernel comparison (BENCH_2)."""
    lines = []
    for name, r in zip(("kernel", "gather"), kern):
        us = r["dt"] / max(r["tok"], 1) * 1e6
        lines.append(f"serve/paged_{name}/tok_s,{us:.0f},{r['tok_s']:.1f}")
    lines.append(f"serve/paged_kernel_vs_gather,0,"
                 f"{kern[0]['tok_s_vs_gather']:.2f}")
    us = long_row["dt"] / max(long_row["tok"], 1) * 1e6
    lines.append(f"serve/long_ctx/ctx,{us:.0f},{long_row['ctx']}")
    lines.append(f"serve/long_ctx/tok_s,{us:.0f},{long_row['tok_s']:.1f}")
    lines.append(f"serve/long_ctx/reserved_cache_kb,{us:.0f},"
                 f"{long_row['reserved_cache_bytes'] / 1024:.1f}")
    return lines


def write_bench_json(out: list[dict], mem: list[dict] | None,
                     path: str | Path = "BENCH_1.json") -> None:
    """The per-PR perf artifact — one writer, shared by main(), run.py, CI."""
    fast, legacy = out
    doc = {
        "bench": "serve_fast_path",
        "arch": "h2o-danube-1.8b (smoke)",
        "serve_tok_s": fast["tok_s"],
        "serve_tok_s_legacy": legacy["tok_s"],
        "speedup_fast_over_legacy": fast["speedup_vs_legacy"],
        "prefill_compiles_fast": fast["prefill_compiles"],
        "prefill_compiles_legacy": legacy["prefill_compiles"],
        "distinct_prompt_lens": fast["distinct_prompt_lens"],
        "f_ratio": fast["f"],
    }
    if mem:
        dense, paged = mem
        doc.update({
            "paged_arch": paged["arch"] + " (smoke)",
            "paged_tok_s": paged["tok_s"],
            "paged_tok_s_vs_dense": paged["tok_s_vs_dense"],
            "paged_reserved_cache_bytes": paged["reserved_cache_bytes"],
            "dense_reserved_cache_bytes": dense["reserved_cache_bytes"],
            "paged_max_ctx_at_dense_hbm": paged["max_ctx_at_dense_hbm"],
            "dense_max_ctx": dense["max_ctx_at_dense_hbm"],
        })
    Path(path).write_text(json.dumps(doc, indent=2) + "\n")


def write_bench2_json(kern: list[dict], long_row: dict,
                      path: str | Path = "BENCH_2.json") -> None:
    """PR 3 perf artifact: in-kernel page-table decode vs. the gathered
    view, plus the long-context point the dense cache cannot represent."""
    kernel, gather = kern
    doc = {
        "bench": "paged_kernel_decode",
        "arch": kernel["arch"] + " (smoke)",
        "table_pages": KERNEL_MAX_LEN // PAGE_SIZE,
        "provisioned_max_len": KERNEL_MAX_LEN,
        "paged_kernel_tok_s": kernel["tok_s"],
        "paged_gather_tok_s": gather["tok_s"],
        "paged_kernel_vs_gather": kernel["tok_s_vs_gather"],
        "long_ctx": long_row["ctx"],
        "long_ctx_tok_s": long_row["tok_s"],
        "long_ctx_reserved_cache_bytes": long_row["reserved_cache_bytes"],
        "long_ctx_dense_equiv_cache_bytes":
            long_row["dense_equiv_cache_bytes"],
        "dense_max_ctx": long_row["dense_max_ctx"],
        "all_done": bool(kernel["all_done"] and gather["all_done"]
                         and long_row["all_done"]),
    }
    Path(path).write_text(json.dumps(doc, indent=2) + "\n")


def main() -> None:
    out = rows()
    mem = paged_rows()
    kern = kernel_rows()
    long_row = long_ctx_row()
    mt = multi_tier_rows()
    sp = spec_decode_rows()
    ft = fault_rows()
    fast, legacy = out
    dense, paged = mem
    print("name,us_per_call,derived")
    for line in csv_rows(out, mem):
        print(line)
    for line in kernel_csv_rows(kern, long_row):
        print(line)
    for line in multi_csv_rows(mt):
        print(line)
    for line in spec_csv_rows(sp):
        print(line)
    for line in fault_csv_rows(ft):
        print(line)
    write_bench_json(out, mem)
    write_bench2_json(kern, long_row)
    write_bench3_json(mt)
    write_bench4_json(sp)
    write_bench5_json(ft)
    print(f"# fast: {fast['tok']} tok in {fast['dt']:.2f}s "
          f"({fast['tok_s']:.1f} tok/s), {fast['prefill_compiles']} prefill "
          f"compiles for {fast['distinct_prompt_lens']} distinct lengths, "
          f"f={fast['f']:.2f}, balance {fast['mean_admitted_per_cycle']:.2f} "
          f"admits / {fast['mean_decoded_per_cycle']:.1f} decodes per cycle")
    print(f"# legacy: {legacy['tok']} tok in {legacy['dt']:.2f}s "
          f"({legacy['tok_s']:.1f} tok/s), {legacy['prefill_compiles']} "
          f"prefill compiles")
    print(f"# paged ({paged['arch']}): {paged['tok_s']:.1f} tok/s "
          f"({paged['tok_s_vs_dense']:.2f}× dense), reserved cache "
          f"{paged['reserved_cache_bytes'] / 1024:.0f} KiB vs dense "
          f"{dense['reserved_cache_bytes'] / 1024:.0f} KiB, max single "
          f"context at dense HBM {paged['max_ctx_at_dense_hbm']} vs "
          f"{dense['max_ctx_at_dense_hbm']} tokens")
    print(f"# paged kernel (max_len {KERNEL_MAX_LEN}): "
          f"{kern[0]['tok_s']:.1f} tok/s vs gather {kern[1]['tok_s']:.1f} "
          f"({kern[0]['tok_s_vs_gather']:.2f}×)")
    print(f"# long ctx: {long_row['ctx']} tokens (dense rows top out at "
          f"{long_row['dense_max_ctx']}) at {long_row['tok_s']:.1f} tok/s, "
          f"pool {long_row['reserved_cache_bytes'] / 1024:.0f} KiB vs "
          f"{long_row['dense_equiv_cache_bytes'] / 1024:.0f} KiB dense rows "
          f"at the same provisioning")
    print(f"# multi-tier: {mt[0]['tok_s']:.1f} tok/s vs best single tier "
          f"(long alone) {mt[1]['tok_s']:.1f} "
          f"({mt[0]['tok_s_vs_best_single']:.2f}×), routed "
          f"{ {n: t['routed'] for n, t in mt[0]['tiers'].items()} }, "
          f"token_equiv={mt[0]['token_equiv']}")
    assert fast["all_done"] and legacy["all_done"]
    assert dense["all_done"] and paged["all_done"]
    assert paged["reserved_cache_bytes"] < dense["reserved_cache_bytes"], (
        "paged pool must reserve less HBM than dense rows")
    assert kern[0]["all_done"] and kern[1]["all_done"] \
        and long_row["all_done"]
    assert long_row["ctx"] > long_row["dense_max_ctx"]
    assert long_row["reserved_cache_bytes"] < \
        long_row["dense_equiv_cache_bytes"], (
            "long-context pool must undercut dense rows at the same "
            "provisioned max_len")
    assert mt[0]["all_done"] and mt[1]["all_done"]
    assert mt[0]["token_equiv"], (
        "multi-tier greedy streams must match the single engine")
    assert mt[0]["tok_s_vs_best_single"] > 1.0, (
        "tier pool must beat the best single tier on the mixed workload")
    k4 = next(r for r in sp["rows"] if r["spec_k"] == 4)
    print(f"# spec decode: target-only {sp['base']['tok_s']:.1f} tok/s; "
          + ", ".join(f"k={r['spec_k']}: {r['tok_s']:.1f} "
                      f"({r['speedup']:.2f}×, acc {r['acceptance']:.2f})"
                      for r in sp["rows"])
          + f"; acceptance by temperature {sp['acceptance_by_temperature']}")
    assert all(r["all_done"] for r in sp["rows"]) and sp["base"]["all_done"]
    assert all(r["token_equiv"] for r in sp["rows"]), (
        "greedy speculative streams must match target-only decode")
    assert k4["speedup"] > 1.3, (
        f"spec_k=4 must beat target-only by >1.3× (got {k4['speedup']:.2f})")
    print(f"# degraded mode: faulted pool {ft['faulted']['tok_s']:.1f} tok/s "
          f"vs healthy {ft['healthy']['tok_s']:.1f} "
          f"({ft['degraded_ratio']:.2f}×), {ft['faulted']['retries']} "
          f"retries, {ft['faulted']['reclaims']} reclaimed, recovery in "
          f"{ft['recovery_cycles']} cycles, leaked_pages="
          f"{ft['leaked_pages']}, token_equiv={ft['token_equiv']}")
    assert ft["healthy"]["all_done"] and ft["faulted"]["all_done"]
    assert ft["faulted"]["dead_letters"] == 0
    assert ft["token_equiv"], (
        "degraded-mode greedy streams must match the healthy pool")
    assert ft["leaked_pages"] == 0, (
        f"tier failure leaked {ft['leaked_pages']} pages")


if __name__ == "__main__":
    main()
