"""Unit tests for the loop-aware HLO analyzer on synthetic HLO text, plus
an end-to-end validation against analytic FLOPs (subprocess: needs 8 host
devices)."""
import os
import subprocess
import sys
import textwrap

from repro.launch.hlo_analysis import _type_bytes, analyze_hlo

SYNTH = textwrap.dedent("""
    HloModule test

    %body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
      %p = (s32[], f32[8,8]) parameter(0)
      %ag = f32[8,8]{1,0} all-gather(%x), replica_groups=[4,2]<=[8], dimensions={0}
      %d = f32[8,8]{1,0} dot(%ag, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %w = f32[8,8]{1,0} parameter(1)
      %x = f32[4,8]{1,0} parameter(2)
    }

    %cond.1 (p: (s32[], f32[8,8])) -> pred[] {
      %p2 = (s32[], f32[8,8]) parameter(0)
      %c = s32[] constant(5)
      %i = s32[] get-tuple-element(%p2), index=0
      %lt = pred[] compare(%i, %c), direction=LT
    }

    ENTRY %main (a: f32[4,8]) -> f32[8,8] {
      %a = f32[4,8]{1,0} parameter(0)
      %t = (s32[], f32[8,8]) tuple(...)
      %wh = (s32[], f32[8,8]) while(%t), condition=%cond.1, body=%body.1
      %ar = f32[8,8]{1,0} all-reduce(%a2), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
      %a2 = f32[8,8]{1,0} parameter(1)
    }
""")


def test_type_bytes():
    assert _type_bytes("f32[8,8]{1,0}") == 256
    assert _type_bytes("bf16[2,4]{1,0}") == 16
    assert _type_bytes("(f32[4]{0}, bf16[4]{0})") == 24
    assert _type_bytes("pred[]") == 1


def test_loop_multiplier_and_wire_model():
    res = analyze_hlo(SYNTH, 8)
    # all-gather in 5-trip loop: out 256B, g=2 → wire 128 × 5 = 640
    # all-reduce in main: 2·256·(4-1)/4 = 384
    assert res["collective_counts"]["n_all-gather"] == 5
    assert res["collective_counts"]["n_all-reduce"] == 1
    assert abs(res["collective_bytes_per_device"] - (640 + 384)) < 1e-6
    # f32 normalization halves everything here
    assert abs(res["collective_bytes_per_device_bf16norm"]
               - (640 + 384) / 2) < 1e-6
    # dot in loop: 2·64·8 = 1024 × 5
    assert res["dot_flops_per_device"] == 1024 * 5


E2E = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.sharding.axes import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    D, F, L, B = 64, 128, 5, 16
    def model(params, x):
        def body(h, w):
            w1, w2 = w
            h = jnp.maximum(h @ w1, 0) @ w2
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, P("data", "model")))
            return h, None
        return jax.lax.scan(body, x, params)[0].mean()
    p = (jax.ShapeDtypeStruct((L, D, F), jnp.float32,
                              sharding=NamedSharding(mesh, P(None, "data", "model"))),
         jax.ShapeDtypeStruct((L, F, D), jnp.float32,
                              sharding=NamedSharding(mesh, P(None, "model", "data"))))
    x = jax.ShapeDtypeStruct((B, D), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", "model")))
    with mesh:
        c = jax.jit(jax.grad(model)).lower(p, x).compile()
    res = analyze_hlo(c.as_text(), 8)
    analytic = 3 * 2 * B * D * F * 2 * L / 8   # fwd+bwd dots per device
    ratio = res["dot_flops_per_device"] / analytic
    assert 0.9 < ratio < 1.2, ratio
    print("E2E-OK", ratio)
""")


def test_analyzer_matches_analytic_flops():
    r = subprocess.run([sys.executable, "-c", E2E],
                       env=dict(os.environ, PYTHONPATH="src"),
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "E2E-OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]
