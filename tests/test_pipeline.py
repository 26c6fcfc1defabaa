"""Pipeline parallelism: pipelined ≡ sequential reference (4-stage mesh,
subprocess for the multi-device runtime)."""
import os
import subprocess
import sys
import textwrap

from repro.train.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == 3 / 15
    assert bubble_fraction(1, 8) == 0.0


PIPE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.sharding.axes import make_mesh
    from repro.train.pipeline import pipeline_apply
    S, M, mb, D = 4, 8, 2, 16
    mesh = make_mesh((4,), ("stage",))
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (S, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))

    def layer_fn(W, h):
        return jnp.tanh(h @ W)

    with mesh:
        out = pipeline_apply(mesh, layer_fn, Ws, x)
    # sequential reference
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ Ws[s])
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, err
    print("PIPE-OK", err)
""")


def test_pipeline_matches_sequential():
    r = subprocess.run([sys.executable, "-c", PIPE],
                       env=dict(os.environ, PYTHONPATH="src"),
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PIPE-OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]
