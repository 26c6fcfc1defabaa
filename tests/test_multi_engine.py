"""Multi-engine heterogeneous serving tiers: routing law, work-conserving
rebalancing, stall/pool backpressure rerouting, and multi-tier ≡
single-engine token equivalence at temperature=0."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import all_configs, smoke_config
from repro.serve.engine import (EngineStallError, PromptTooLongError,
                                Request, StepReport, make_engine,
                                worst_case_pages)
from repro.serve.multi_engine import MultiEngine, make_multi_engine
from repro.serve.scheduler import request_units, route_requests, tier_speeds

ARCH = "mistral-nemo-12b"          # full attention → paged tiers exercised


def _cfg():
    return smoke_config(all_configs()[ARCH])


def _prompts(n, lo=4, hi=31, seed=3, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(x)).tolist()
            for x in rng.integers(lo, hi, n)]


# ------------------------------------------------------------ pure routing
def test_route_requests_converges_to_proportional_shares():
    """Skewed per-tier throughput → cumulative token-unit shares converge
    to the proportional law (3:1 within a few percent), with FIFO order
    preserved per tier. Pure host code: no engines, no timing."""
    speeds = [3.0, 1.0]
    done = [0, 0]
    rng = np.random.default_rng(0)
    for _ in range(200):
        units = [int(u) for u in rng.integers(5, 40, 8)]
        assign = route_requests(units, speeds, capacities=[8, 8])
        for i, idxs in enumerate(assign):
            assert idxs == sorted(idxs)            # FIFO within tier
            done[i] += sum(units[j] for j in idxs)
        assert sorted(assign[0] + assign[1]) == list(range(len(units)))
    share = done[0] / (done[0] + done[1])
    assert abs(share - 0.75) < 0.05, (done, share)


def test_route_requests_capacity_and_spill():
    """A tier with no capacity takes nothing; its share spills to the live
    tiers; requests beyond aggregate capacity stay queued."""
    units = [10, 10, 10, 10, 10]
    a = route_requests(units, [1.0, 5.0], [3, 0])
    assert a[1] == [] and a[0] == [0, 1, 2]        # spill + backpressure
    a = route_requests(units, [1.0, 5.0], [0, 0])
    assert a == [[], []]
    with pytest.raises(ValueError):
        route_requests(units, [1.0], [1, 1])


def test_route_requests_eligibility_and_constrained_first():
    """A request eligible on only one tier claims that tier's scarce
    capacity before universally-eligible requests spill onto it."""
    units = [10, 10, 10, 30]                       # last: long request
    eligible = [[True, True]] * 3 + [[False, True]]
    a = route_requests(units, [1.0, 1.0], [2, 1], eligible)
    assert 3 in a[1] and 3 not in a[0]
    assert len(a[0]) == 2 and len(a[1]) == 1       # capacity respected
    # nothing eligible anywhere stays queued rather than erroring
    a = route_requests([5], [1.0, 1.0], [1, 1], [[False, False]])
    assert a == [[], []]


def test_tier_speeds_prior_and_unit_cost():
    assert tier_speeds([0.0, 100.0], [2.0, 1.0], [1.0, 4.0]) == [2.0, 25.0]
    assert request_units(10, 6) == 16
    assert request_units(0, 0) == 1


# ------------------------------------------------------- engine tier surface
def test_step_report_and_tier_interface(ctx):
    """Engine.step exposes per-quantum token throughput; plan_admission and
    take_pending give a router slot- and pool-aware control."""
    cfg = _cfg()
    eng = make_engine(cfg, ctx, max_slots=2, max_len=64, decode_quantum=4)
    reqs = [Request(rid=i, prompt=p, max_new=6)
            for i, p in enumerate(_prompts(3, vocab=cfg.vocab))]
    assert eng.plan_admission(reqs) == 2           # slot-capped
    for r in reqs:
        eng.submit(r)
    assert eng.has_work()
    rep = eng.step()
    assert isinstance(rep, StepReport)
    assert rep.admitted >= 1 and rep.decoded >= 1 and rep.dt > 0
    left = eng.take_pending()                      # un-admitted work back
    assert eng.pending == [] and all(isinstance(r, Request) for r in left)
    for r in left:
        eng.submit(r)
    eng.drain()
    assert not eng.has_work() and all(r.done for r in reqs)
    assert eng.decode_throughput() > 0


def test_plan_admission_pool_capped(ctx):
    """A paged engine's plan_admission stops at the pool's worst-case
    commit budget, not just at free slots."""
    cfg = _cfg()
    pages = 1 + 64 // 8                            # one full context only
    eng = make_engine(cfg, ctx, max_slots=4, max_len=64, paged=True,
                      page_size=8, num_pages=pages)
    reqs = [Request(rid=i, prompt=[1] * 40, max_new=20) for i in range(3)]
    assert eng.plan_admission(reqs) == 1, (
        "pool holds one worst-case context; admission must stop there")


# ----------------------------------------------------------- pool behaviour
def test_multi_engine_validation(ctx):
    cfg = _cfg()
    with pytest.raises(ValueError):
        MultiEngine([])
    meng = make_multi_engine(cfg, ctx, [{"name": "a"}, {"name": "b"}],
                             max_slots=2, max_len=64)
    with pytest.raises(ValueError):                # duplicate names
        make_multi_engine(cfg, ctx, [{"name": "a"}, {"name": "a"}],
                          max_slots=2, max_len=64)
    with pytest.raises(ValueError):                # shared engine object
        MultiEngine([type(meng.tiers[0])("x", meng.tiers[0].engine),
                     type(meng.tiers[0])("y", meng.tiers[0].engine)])
    with pytest.raises(ValueError):
        make_multi_engine(cfg, ctx, [{"name": "a", "kind": "gpu"}],
                          max_slots=2, max_len=64)
    with pytest.raises(ValueError):
        meng.submit(Request(rid=0, prompt=[], max_new=2))
    with pytest.raises(PromptTooLongError):        # too long for EVERY tier
        meng.submit(Request(rid=0, prompt=[1] * 64, max_new=2))


def test_multi_tier_token_equivalence(ctx):
    """The same workload through a heterogeneous dense+paged pool and
    through one engine produces identical greedy streams per request —
    which tier served a request must not change its tokens."""
    cfg = _cfg()
    prompts = _prompts(7, vocab=cfg.vocab)
    meng = make_multi_engine(cfg, ctx, [
        {"name": "dense"},
        {"name": "paged", "paged": True, "page_size": 8},
    ], max_slots=2, max_len=64, decode_quantum=4)
    multi = [Request(rid=i, prompt=p, max_new=1 if i == 2 else 6)
             for i, p in enumerate(prompts)]
    meng.run(multi)
    assert all(r.done for r in multi)
    # both tiers actually served part of the workload
    assert all(t.routed > 0 for t in meng.tiers), meng.stats()
    assert set(meng.assigned) == {r.rid for r in multi}
    eng = make_engine(cfg, ctx, max_slots=2, max_len=64, decode_quantum=4)
    single = [Request(rid=i, prompt=p, max_new=1 if i == 2 else 6)
              for i, p in enumerate(prompts)]
    eng.run(single)
    for a, b in zip(multi, single):
        assert a.out == b.out, (a.rid, meng.assigned[a.rid], a.out, b.out)


def test_multi_tier_long_prompt_routes_to_capable_tier(ctx):
    """Prompts too long for the short tier are only eligible on the long
    tier; shorts and longs complete side by side."""
    cfg = _cfg()
    meng = make_multi_engine(cfg, ctx, [
        {"name": "short", "max_len": 48},
        {"name": "long", "max_len": 128},
    ], max_slots=2, decode_quantum=4)
    reqs = [Request(rid=0, prompt=_prompts(1, 90, 91, vocab=cfg.vocab)[0],
                    max_new=4)]
    reqs += [Request(rid=1 + i, prompt=p, max_new=4)
             for i, p in enumerate(_prompts(3, vocab=cfg.vocab))]
    meng.run(reqs)
    assert all(r.done for r in reqs)
    assert meng.assigned[0] == "long"


def test_stalled_tier_reroutes_work(ctx):
    """All slots of one tier are pinned by a long-running request; queued
    work must flow through the other tier instead of blocking (work
    conservation), and the pool must not stall."""
    cfg = _cfg()
    meng = make_multi_engine(cfg, ctx, [{"name": "a"}, {"name": "b"}],
                             max_slots=1, max_len=64, decode_quantum=2,
                             concurrent=False)
    blocker = Request(rid=99, prompt=[1, 2, 3], max_new=40)
    tier_b = meng.tiers[1]
    tier_b.engine.submit(blocker)                  # pin b's only slot
    tier_b.engine.step()
    assert not tier_b.engine.free_slots()
    shorts = [Request(rid=i, prompt=p, max_new=3)
              for i, p in enumerate(_prompts(4, vocab=cfg.vocab))]
    meng.run(shorts)
    assert all(r.done for r in shorts)
    assert all(meng.assigned[r.rid] == "a" for r in shorts), meng.assigned
    tier_b.engine.drain()                          # let the blocker finish
    assert blocker.done


def test_pool_exhausted_tier_reroutes_work(ctx):
    """A paged tier whose pool cannot commit another request has zero
    effective capacity; queued work reroutes to the dense tier."""
    cfg = _cfg()
    pages = 1 + 64 // 8                            # one worst-case context
    meng = make_multi_engine(cfg, ctx, [
        {"name": "dense"},
        {"name": "paged", "paged": True, "page_size": 8,
         "num_pages": pages},
    ], max_slots=2, max_len=64, decode_quantum=2, concurrent=False)
    # the hog's worst case (prompt + max_new − 1 + quantum ≥ max_len) commits
    # every pool page, and its 50-token budget outlasts the whole short run
    hog = Request(rid=99, prompt=[1] * 10, max_new=50)
    paged = meng.tiers[1]
    paged.engine.submit(hog)                       # commits the whole pool
    paged.engine.step()
    assert paged.engine.plan_admission(
        [Request(rid=98, prompt=[1] * 8, max_new=8)]) == 0
    reqs = [Request(rid=i, prompt=p, max_new=3)
            for i, p in enumerate(_prompts(4, vocab=cfg.vocab))]
    meng.run(reqs)
    assert all(r.done for r in reqs)
    assert all(meng.assigned[r.rid] == "dense" for r in reqs), meng.assigned
    paged.engine.drain()
    assert hog.done


def test_multi_engine_throughput_routing_skew(ctx):
    """With strongly skewed *measured* tier speeds, the proportional law
    routes most requests to the fast tier. Deterministic: the shared
    tracker is primed by hand instead of timing real quanta."""
    cfg = _cfg()
    meng = make_multi_engine(cfg, ctx, [{"name": "fast"}, {"name": "slow"}],
                             max_slots=6, max_len=64, decode_quantum=4,
                             concurrent=False)
    for _ in range(6):                             # converge the EWMA
        meng.tracker.record("fast", 900, 1.0)
        meng.tracker.record("slow", 100, 1.0)
    # capacity is NOT binding (12 slots, 6 requests), so the deficit law —
    # not work-conserving spill — decides every placement
    reqs = [Request(rid=i, prompt=p, max_new=4)
            for i, p in enumerate(_prompts(6, vocab=cfg.vocab))]
    meng.run(reqs)
    assert all(r.done for r in reqs)
    fast = sum(1 for r in reqs if meng.assigned[r.rid] == "fast")
    assert fast >= 4, meng.assigned


def test_multi_engine_stall_reports_per_tier(ctx):
    """A hung tier (its step makes no progress — the analogue of a wedged
    device) trips the pool's guard with per-tier diagnostics instead of
    spinning forever."""
    cfg = _cfg()
    meng = make_multi_engine(cfg, ctx, [{"name": "only"}],
                             max_slots=1, max_len=64, decode_quantum=2,
                             concurrent=False)
    meng.tiers[0].engine.step = lambda: StepReport()    # hung device
    with pytest.raises(EngineStallError, match="only:"):
        meng.run([Request(rid=1, prompt=[4, 5], max_new=2)])


_PER_DEVICE_POOL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from repro.configs import all_configs, smoke_config
    from repro.serve.engine import Engine, Request
    from repro.serve.multi_engine import make_multi_engine
    from repro.sharding.axes import single_device_ctx

    cfg = smoke_config(all_configs()["mistral-nemo-12b"])
    devs = jax.devices()
    assert len(devs) == 4, devs
    ctxs = [single_device_ctx(d) for d in devs]
    kw = dict(max_slots=2, max_len=64, decode_quantum=4, paged=True,
              page_size=8)
    meng = make_multi_engine(cfg, ctxs[0], [
        {"name": f"dev{i}", "ctx": c} for i, c in enumerate(ctxs)], **kw)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, int(n)).tolist()
               for n in rng.integers(4, 40, 8)]
    multi = [Request(rid=i, prompt=p, max_new=6)
             for i, p in enumerate(prompts)]
    meng.run(multi)
    for i, tier in enumerate(meng.tiers):
        leaves = (jax.tree.leaves(tier.engine.params)
                  + jax.tree.leaves(tier.engine.cache)
                  + [tier.engine.page_table_dev, tier.engine.tokens_dev])
        assert all(x.devices() == {devs[i]} for x in leaves), tier.name
        assert tier.routed > 0, meng.stats()
    # one replica on device 0, sharing tier 0's weights
    single = Engine(cfg, meng.tiers[0].engine.params, ctxs[0], **kw)
    ref = [Request(rid=i, prompt=p, max_new=6)
           for i, p in enumerate(prompts)]
    single.run(ref)
    for a, b in zip(multi, ref):
        assert a.done and a.out == b.out, (a.rid, a.out, b.out)
    print("PER-DEVICE-POOL-OK")
""")


def test_per_device_replica_pool_matches_single_replica():
    """Four one-device replicas (tier i's weights, cache and state on
    device i) serve greedy streams identical to one replica; every tier
    takes work. Four host devices stand in for four chips."""
    r = subprocess.run([sys.executable, "-c", _PER_DEVICE_POOL],
                       env=dict(os.environ, PYTHONPATH="src",
                                JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PER-DEVICE-POOL-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-2000:]
