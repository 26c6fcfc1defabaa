"""The serving engine's own telemetry: the three request stamps, the two
prefill counters, and the six ``engine.*`` host spans in a profiler
trace."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import all_configs, smoke_config
from repro.serve.engine import Request, make_engine
from repro.serve.faults import Fault, FaultyEngine
from repro.serve.multi_engine import HealthPolicy, make_multi_engine

SPANS = ("engine.admit", "engine.prefill", "engine.pages", "engine.decode",
         "engine.fetch", "engine.retire")


def _cfg():
    return smoke_config(all_configs()["mistral-nemo-12b"])


def _reqs(cfg, lens, max_new=6, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n).tolist(),
                    max_new=max_new) for i, n in enumerate(lens)]


def _server(cfg, ctx, kind):
    kw = dict(max_slots=2, max_len=64, decode_quantum=4)
    if kind == "engine":
        return make_engine(cfg, ctx, paged=True, page_size=8, **kw)
    return make_multi_engine(cfg, ctx, [
        {"name": "dense"}, {"name": "paged", "paged": True, "page_size": 8},
    ], concurrent=False, **kw)


@pytest.mark.parametrize("kind", ["engine", "multi"])
def test_stamps_are_ordered(ctx, kind):
    """Every served request was submitted, then admitted, then got its
    first token; the stamps are on the clock of time.perf_counter()."""
    cfg = _cfg()
    server = _server(cfg, ctx, kind)
    reqs = _reqs(cfg, [4, 9, 17, 30, 5])     # more than one tier's slots
    assert all(r.t_submit is None for r in reqs)
    server.run(reqs)
    for r in reqs:
        assert r.done
        assert r.t_submit <= r.t_admit <= r.t_first, r


def test_retried_request_keeps_its_first_submit(ctx):
    """A request reclaimed from a failed tier and served again keeps the
    stamp of its first submit(); its later stamps stay in order."""
    cfg = _cfg()
    meng = make_multi_engine(
        cfg, ctx, [{"name": "only", "paged": True, "page_size": 8}],
        max_slots=2, max_len=64, decode_quantum=4, concurrent=False,
        policy=HealthPolicy(quarantine_after=1, quarantine_cycles=1,
                            probation_steps=1, retry_backoff=0))
    only = meng.tiers[0]
    only.engine = FaultyEngine(only.engine, [Fault(kind="raise", at=(1,))])
    reqs = _reqs(cfg, [4, 9, 17])
    for r in reqs:
        meng.submit(r)
    first = [r.t_submit for r in reqs]
    while meng.has_work():
        meng.step()
    assert meng.retries > 0
    assert all(r.done for r in reqs) and not meng.dead_letters
    assert [r.t_submit for r in reqs] == first
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first


def test_prefill_counters_cover_every_group(ctx):
    """prefill_tokens is the prompt tokens served, compiling groups
    included; prefill_s is the time of the groups' spans."""
    cfg = _cfg()
    eng = _server(cfg, ctx, "engine")
    reqs = _reqs(cfg, [4, 9, 17, 30])
    eng.run(reqs)
    assert eng.prefill_tokens == sum(len(r.prompt) for r in reqs)
    assert eng.prefill_s > 0
    # a second run adds to both; nothing resets them
    s0, n0 = eng.prefill_s, eng.prefill_tokens
    more = _reqs(cfg, [6, 7], seed=4)
    eng.run(more)
    assert eng.prefill_tokens == n0 + 13 and eng.prefill_s > s0


def test_profiler_trace_holds_every_engine_span(ctx, tmp_path):
    cfg = _cfg()
    eng = _server(cfg, ctx, "engine")
    eng.run(_reqs(cfg, [4, 9]))                     # compile outside
    with jax.profiler.trace(str(tmp_path)):
        eng.run(_reqs(cfg, [5, 11], seed=5))
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                                "*.xplane.pb")))[-1]
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert set(SPANS) <= names, sorted(n for n in names
                                       if n.startswith("engine."))
