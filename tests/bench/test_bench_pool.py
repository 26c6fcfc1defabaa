"""A configuration with ``replicas: 4`` built and driven by the harness
on four virtual CPU devices, one tier per device: the path the queued
four-chip pool cell will take, checked here without the chip."""
import os
import subprocess
import sys
import textwrap

from bench_tiny import ROOT

_POOL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "tests", "bench")]
    import jax
    from bench import harness
    from bench.serving import MultiEngine, model_shape
    from bench_tiny import tiny_cell
    from repro.configs import get_config, smoke_config
    from bench import serving
    serving.get_config = lambda n: smoke_config(get_config(n))
    cell = tiny_cell("tiny-nemo", chips=4, replicas=4)
    devs = jax.devices()
    assert len(devs) == 4, devs
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
    del server
    gaps = harness.check(model_shape(cell.config), 3, reqs)
    assert gaps["program"] <= cell.config["limits"]["max_logit_gap"], gaps
    print("POOL-HARNESS-OK")
""")


def test_replica_pool_cell_runs_through_the_harness():
    r = subprocess.run([sys.executable, "-c", _POOL, str(ROOT)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=str(ROOT / "src")),
                       capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert "POOL-HARNESS-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
