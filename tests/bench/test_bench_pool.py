"""A cell on four chips built and driven by the harness on four virtual
CPU devices: the unchanged one-chip configuration served as one replica
per device behind the program's router, the path of the four-chip pool
cell, checked here without the chip. A sound pool passes the check and
its float8 control fails it; faults planted under a whole pool run come
out as not correct. (The replicas exchange nothing between chips, so
there is no exchange to leave out.)"""
import os
import subprocess
import sys
import textwrap

import pytest

from bench_tiny import ROOT

_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "tests", "bench")]
    import time
    import jax
    import jax.numpy as jnp
    import repro.configs as registry
    from bench import harness, manifest
    from bench.serving import MultiEngine
    from bench_tiny import PEAKS, tiny_cell
    real = registry.get_config
    registry.get_config = lambda n: registry.smoke_config(real(n))
    cell = tiny_cell("tiny-nemo", chips=4)
    assert "replicas" not in cell.config
    devs = jax.devices()
    assert len(devs) == 4, devs
""")

_SOUND = _PRELUDE + textwrap.dedent("""
    server, arrivals = harness.prepare(cell, 3, 3.0, devs)
    assert isinstance(server, MultiEngine) and len(server.tiers) == 4
    for d, tier in zip(devs, server.tiers):
        leaves = (jax.tree.leaves(tier.engine.params)
                  + jax.tree.leaves(tier.engine.cache))
        assert all(x.devices() == {d} for x in leaves), tier.name
    clock = harness.CompileClock()
    reqs, stats, steps, facts = harness.measure(server, arrivals, 3.0,
                                                False, clock)
    assert facts["failed"] == 0 and facts["sent"] == len(reqs) == 18
    assert facts["compiles_in_window"] == 0, facts
    # every tier was warmed on its own device; how the router spreads a
    # trickle of arrivals is the program's to decide, not checked here
    assert all(t.engine.prefill_compiles() > 0 for t in server.tiers)
    assert sum(s.emitted for s in steps) == sum(len(r.out) for r in reqs)
    assert sum(sum(s.per_tier.values()) for s in steps) == sum(
        s.decode_tokens for s in steps)
    rec = harness.record(cell, server, 3.0, PEAKS, stats, steps, facts)
    assert rec.tiers == ("chip0", "chip1", "chip2", "chip3"), rec.tiers
    ratio = manifest.reader("router.tier_load_ratio")(rec)
    assert 1.0 <= ratio <= 4.0, ratio
    del server
    gaps = harness.check(cell.config, 3, reqs, controls=("fp8",))
    limit = cell.config["limits"]["max_logit_gap"]
    assert gaps["program"] <= limit < gaps["controls"]["fp8"], gaps
    print("POOL-HARNESS-OK")
""")

_FAULTS = {
    # a decoded token altered where it is produced
    "token-altered": """
from repro.serve import decode
real_sample = decode._sample_tokens
decode._sample_tokens = lambda logits, key, **kw: (
    real_sample(logits, key, **kw) + 1) % logits.shape[-1]
""",
    # the decode step returns its cache unchanged
    "state-unchanged": """
from repro.serve import decode
decode._local_write = lambda cache, row, rel: cache
decode._paged_write = lambda pool, row, pt, pos, i, msize: pool
""",
}

_FAULTY = _PRELUDE + textwrap.dedent("""
    from bench.harness import run_cell
    exec(sys.argv[2])
    out = run_cell(cell, 11, 2.0, False, time.perf_counter(), PEAKS, devs)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"], gap
    print("POOL-FAULT-CAUGHT")
""")


def _run(script: str, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", script, str(ROOT), *args],
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   PYTHONPATH=str(ROOT / "src")),
                          capture_output=True, text=True, timeout=600,
                          cwd=ROOT)


def test_replica_pool_cell_runs_through_the_harness():
    r = _run(_SOUND)
    assert "POOL-HARNESS-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


@pytest.mark.parametrize("fault", sorted(_FAULTS))
def test_fault_under_the_pool_is_not_correct(fault):
    r = _run(_FAULTY, _FAULTS[fault])
    assert "POOL-FAULT-CAUGHT" in r.stdout, (r.stdout[-2000:]
                                             + r.stderr[-3000:])
