"""Compile a cell's serving programs for a described TPU v5e, without the
chip, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload nemo-l8.chat
        [--max-slots N] [--num-pages N] [--prefill-batch N]

Builds the engine of the cell's configuration over shapes only (no array
is made), steers paged attention to the Pallas kernel, and compiles, at
the cell's sizes: the weight init, prefill and the admit scatter for each
prompt-length bucket the traffic uses, and the decode quantum for each
live page-table width it can reach. ``memory_analysis`` of each is
printed with the bytes that would be live at its peak: the weights and
cache it takes as arguments, its output less what it aliases, and its
temporaries. The engine settings in ``bench/configs`` were fixed from
this.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
GB = 1e9


def _report(name: str, compiled, resident: float) -> float:
    m = compiled.memory_analysis()
    args, out = m.argument_size_in_bytes, m.output_size_in_bytes
    alias, temp = m.alias_size_in_bytes, m.temp_size_in_bytes
    peak = resident + out - alias + temp
    print(f"{name:34s} args {args / GB:7.3f} GB  out {out / GB:7.3f}  "
          f"alias {alias / GB:7.3f}  temp {temp / GB:7.3f}  -> live at "
          f"peak {peak / GB:7.3f} GB", flush=True)
    return peak


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--max-slots", type=int)
    ap.add_argument("--num-pages", type=int)
    ap.add_argument("--prefill-batch", type=int)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies

    from bench import manifest
    from bench.harness import warm_requests
    from bench.serving import engine_kwargs
    from bench.weights import root_key
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.serve import engine as engine_mod
    from repro.serve.prefill import bucket_len
    from repro.sharding import params as prm
    from repro.sharding.axes import single_device_ctx

    jax.config.update("jax_enable_compilation_cache", False)
    cell = manifest.load_cell(args.workload)
    conf = cell.config
    for key in ("max_slots", "num_pages", "prefill_batch"):
        if getattr(args, key) is not None:
            conf["engine"][key] = getattr(args, key)
    model = manifest.model(conf)
    cfg = model.program_config(conf)
    shape = model.model_shape(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    ctx = single_device_ctx(dev)
    sh = jax.sharding.SingleDeviceSharding(dev)

    def spec(x):
        return jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                    sharding=sh)

    # shapes instead of arrays, the Pallas kernel instead of the CPU path
    real_put = jax.device_put
    jax.device_put = lambda x, s=None: jax.tree.map(spec, x)
    prm.materialize_sharded = lambda defs, key, c: prm.abstract(defs, c)
    paged_ops._resolve = lambda impl: ("kernel", False)
    kw = engine_kwargs(conf)
    build, defs = model.params_fn(cfg, shape)
    params = prm.abstract(defs, ctx)
    eng = engine_mod.Engine(cfg, params, ctx, **kw)
    jax.device_put = real_put
    print(f"{cell.name}: {conf['registry']} {cfg.n_layers} layers, engine "
          f"{kw}", flush=True)
    weights = prm.param_bytes(defs)
    cache = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(eng.cache))
    print(f"weights {weights / GB:.3f} GB, cache {cache / GB:.3f} GB",
          flush=True)
    resident = weights + cache
    init = jax.jit(build, out_shardings=prm.shardings(defs, ctx)).lower(
        spec(root_key(0))).compile()
    worst = _report("weight init", init, 0.0)
    gen = manifest.generator(cell.traffic["kind"])
    pairs = gen.work(cell.traffic, args.seconds)
    plens = [p for p, _ in pairs]
    max_total = max(p + o for p, o in pairs)
    buckets = sorted({bucket_len(n, min_bucket=eng.min_bucket,
                                 max_bucket=eng.max_len)
                      for n in plens + warm_requests(eng, plens, max_total)})
    P, S = eng.prefill_batch, eng.max_slots
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sh)  # noqa
    key = spec(jax.random.PRNGKey(0))
    state = (eng.tokens_dev, eng.pos_dev, eng.active_dev, eng.remaining_dev)
    for b in buckets:
        lo = eng._prefill_fast.lower(params, i32(P, b), i32(P), key)
        worst = max(worst, _report(f"prefill {P} x {b}", lo.compile(),
                                   resident))
        first, new_cache = jax.tree.map(
            lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=sh),
            lo.out_info)
        extra = (i32(eng.num_pages),) if eng.paged else ()
        adm = eng._admit.lower(eng.cache, *state, new_cache, first, i32(P),
                               i32(P), i32(P),
                               jax.ShapeDtypeStruct((P,), jnp.bool_,
                                                    sharding=sh), *extra)
        worst = max(worst, _report(f"admit {P} x {b}", adm.compile(),
                                   resident))
    loop_args = (eng._loop_params, eng.cache, *state, eng.rng_dev)
    widths = [eng.pages_per_slot] if eng.paged else [None]
    if eng.paged and eng.paged_kernel:
        widths = sorted({min(eng.pages_per_slot, max(8, 1 << (
            -(-(n + eng.quantum_tokens) // eng.page_size) - 1).bit_length()))
            for n in warm_requests(eng, plens, max_total)})
    for w in widths:
        extra = (i32(S, w),) if w else ()
        c = eng._decode_loop.lower(*loop_args, *extra).compile()
        if w == widths[-1]:
            print("  kernel in decode loop:", "tpu_custom_call" in c.as_text())
        worst = max(worst, _report(f"decode quantum {S} slots"
                                   + (f", {w} pages" if w else ""),
                                   c, resident))
    print(f"largest live at a program's peak: {worst / GB:.3f} GB of "
          f"{16:.0f} GB", flush=True)


if __name__ == "__main__":
    main()
