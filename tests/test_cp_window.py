"""Window-aware CP attention (neighbor kv exchange) must equal the
single-device computation — 8-device subprocess, SWA arch (h2o)."""
import os
import subprocess
import sys
import textwrap

CP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import all_configs, smoke_config
    from repro.models.model import model_defs, loss_fn, synth_batch
    from repro.sharding import params as prm
    from repro.sharding.axes import ShardCtx, make_mesh

    cfg = smoke_config(all_configs()["h2o-danube-1.8b"])  # window 32
    params = prm.materialize(model_defs(cfg), jax.random.PRNGKey(0))
    batch = synth_batch(cfg, 4, 64, jax.random.PRNGKey(1))

    # single device reference
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    ref = float(loss_fn(cfg, params, batch, ShardCtx(mesh=mesh1))[0])

    # 4-way model mesh: S_loc=16, window=32 → n_nb=2 < msize-1 → neighbor path
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ShardCtx(mesh=mesh)
    with mesh:
        got = float(jax.jit(lambda p, b: loss_fn(cfg, p, b, ctx)[0])(params, batch))
    err = abs(got - ref)
    assert err < 2e-2, (got, ref)
    print("CPWIN-OK", got, ref)
""")


def test_window_cp_matches_single_device():
    r = subprocess.run([sys.executable, "-c", CP],
                       env=dict(os.environ, PYTHONPATH="src"),
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "CPWIN-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
